"""BASELINE config-3 shape (IOnDiskStateMachine + durable WAL) and the
witness / non-voting membership tiers, end to end.

reference: statemachine/ondisk.go contract (Open returns the SM's own
applied index; dragonboat replays only the tail) and witness/nonVoting
semantics (witness votes + acks metadata-only replication, holds no
data, can never lead; non-voting replicates data but no vote) [U].
"""
import os
import pickle
import shutil
import time

import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    IOnDiskStateMachine,
    NodeHost,
    NodeHostConfig,
    Result,
)
from dragonboat_tpu.storage.tan import tan_logdb_factory
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import nh_dir  # noqa: F401
from test_nodehost import KVStore, propose_r, set_cmd, wait_for_leader

ADDRS = {1: "od-1", 2: "od-2", 3: "od-3"}


class DiskKV(IOnDiskStateMachine):
    """On-disk KV: state lives in the SM's own pickle file; ``open``
    reports the applied index so raft replays only the tail."""

    def __init__(self, shard_id, replica_id):
        self.path = f"/tmp/diskkv-{shard_id}-{replica_id}.pkl"
        self.data = {}
        self.applied = 0
        self.update_calls = 0

    def open(self, stopc) -> int:
        if os.path.exists(self.path):
            with open(self.path, "rb") as f:
                self.applied, self.data = pickle.load(f)
        return self.applied

    def update(self, entries):
        out = []
        for e in entries:
            self.update_calls += 1
            op, k, v = pickle.loads(e.cmd)
            if op == "set":
                self.data[k] = v
            self.applied = e.index
            out.append(
                type(e)(
                    index=e.index, cmd=e.cmd, result=Result(value=len(self.data))
                )
            )
        return out

    def sync(self) -> None:
        tmp = self.path + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump((self.applied, self.data), f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def lookup(self, query):
        return self.data.get(query)

    def prepare_snapshot(self):
        return (self.applied, dict(self.data))

    def save_snapshot(self, ctx, w, done):
        w.write(pickle.dumps(ctx))

    def recover_from_snapshot(self, r, done):
        self.applied, self.data = pickle.loads(r.read())
        self.sync()

    def close(self):
        pass


def make_od_nodehost(rid):
    cfg = NodeHostConfig(
        nodehost_dir=f"/tmp/nh-od-{rid}",
        rtt_millisecond=2,
        raft_address=ADDRS[rid],
        expert=ExpertConfig(
            engine=EngineConfig(exec_shards=2, apply_shards=2),
            logdb_factory=tan_logdb_factory,
        ),
    )
    return NodeHost(cfg)


def od_config(rid, **kw):
    kw.setdefault("election_rtt", 10)
    kw.setdefault("heartbeat_rtt", 1)
    return Config(replica_id=rid, shard_id=1, **kw)


@pytest.fixture
def od_cluster():
    reset_inproc_network()
    for rid in ADDRS:
        shutil.rmtree(f"/tmp/nh-od-{rid}", ignore_errors=True)
        for r2 in (1, 2, 3):
            try:
                os.unlink(f"/tmp/diskkv-1-{r2}.pkl")
            except FileNotFoundError:
                pass
    nhs = {rid: make_od_nodehost(rid) for rid in ADDRS}
    for rid, nh in nhs.items():
        nh.start_replica(ADDRS, False, DiskKV, od_config(rid))
    yield nhs
    for nh in nhs.values():
        nh.close()


class TestOnDiskSM:
    def test_propose_read_on_disk(self, od_cluster):
        wait_for_leader(od_cluster)
        nh = od_cluster[1]
        s = nh.get_noop_session(1)
        for i in range(10):
            propose_r(nh, s, set_cmd(f"od-{i}", str(i).encode()))
        deadline = time.time() + 10.0
        while True:
            try:
                assert od_cluster[2].sync_read(1, "od-9", timeout=2.0) == b"9"
                break
            except AssertionError:
                raise
            except Exception:
                if time.time() > deadline:
                    raise
                time.sleep(0.05)

    def test_open_reports_applied_and_tail_replays(self, od_cluster):
        wait_for_leader(od_cluster)
        nh = od_cluster[1]
        s = nh.get_noop_session(1)
        for i in range(10):
            propose_r(nh, s, set_cmd(f"t-{i}", str(i).encode()))
        # force every replica's SM to persist its own state
        for rid, h in od_cluster.items():
            h._nodes[1].sm.managed.sm.sync()
        for h in od_cluster.values():
            h.close()

        # restart: open() reports the applied index; update() must only
        # see the tail (no double-apply of old entries)
        reset_inproc_network()
        nhs = {rid: make_od_nodehost(rid) for rid in ADDRS}
        try:
            for rid, h in nhs.items():
                h.start_replica(ADDRS, False, DiskKV, od_config(rid))
            wait_for_leader(nhs)
            sm = nhs[1]._nodes[1].sm.managed.sm
            assert sm.data.get("t-9") == b"9"  # recovered from its own file
            s = nhs[1].get_noop_session(1)
            propose_r(nhs[1], s, set_cmd("post", b"x"))
            deadline = time.time() + 10.0
            while True:
                try:
                    assert nhs[2].sync_read(1, "post", timeout=2.0) == b"x"
                    break
                except AssertionError:
                    raise
                except Exception:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.05)
        finally:
            for h in nhs.values():
                h.close()


# ---------------------------------------------------------------------------
# witness / non-voting tiers
# ---------------------------------------------------------------------------
W_ADDRS = {1: "wt-1", 2: "wt-2", 3: "wt-3"}


def make_w_nodehost(rid):
    cfg = NodeHostConfig(
        nodehost_dir=f"/tmp/nh-wt-{rid}",
        rtt_millisecond=2,
        raft_address=W_ADDRS[rid],
        expert=ExpertConfig(
            engine=EngineConfig(exec_shards=2, apply_shards=2)
        ),
    )
    return NodeHost(cfg)


def w_config(rid, **kw):
    kw.setdefault("election_rtt", 10)
    kw.setdefault("heartbeat_rtt", 1)
    return Config(replica_id=rid, shard_id=1, **kw)


@pytest.fixture
def two_plus_one():
    """Shard with voters {1,2}; host 3 idle (joins as witness/non-voting)."""
    reset_inproc_network()
    for rid in W_ADDRS:
        shutil.rmtree(f"/tmp/nh-wt-{rid}", ignore_errors=True)
    nhs = {rid: make_w_nodehost(rid) for rid in W_ADDRS}
    voters = {1: W_ADDRS[1], 2: W_ADDRS[2]}
    for rid in (1, 2):
        nhs[rid].start_replica(voters, False, KVStore, w_config(rid))
    yield nhs
    for nh in nhs.values():
        nh.close()


def retry(fn, deadline=10.0):
    end = time.time() + deadline
    while True:
        try:
            return fn()
        except AssertionError:
            raise
        except Exception:
            if time.time() >= end:
                raise
            time.sleep(0.05)


class TestWitness:
    def test_witness_sustains_quorum_without_data(self, two_plus_one):
        nhs = two_plus_one
        sub = {1: nhs[1], 2: nhs[2]}
        wait_for_leader(sub)
        retry(lambda: nhs[1].sync_request_add_witness(1, 3, W_ADDRS[3]))
        nhs[3].start_replica(
            {}, True, KVStore, w_config(3, is_witness=True)
        )
        time.sleep(0.3)
        s = nhs[1].get_noop_session(1)
        propose_r(nhs[1], s, set_cmd("w1", b"a"))
        # kill voter 2: voter 1 + witness still form a 2/3 quorum
        nhs[2].close()
        retry(
            lambda: propose_r(nhs[1], s, set_cmd("w2", b"b"), deadline=15.0),
            deadline=20.0,
        )
        assert retry(lambda: nhs[1].sync_read(1, "w2", timeout=2.0)) == b"b"
        # the witness held quorum but NO data (metadata-only replication)
        wsm = nhs[3]._nodes[1].sm.managed.sm
        assert wsm.data == {}, wsm.data

    def test_witness_never_leads(self, two_plus_one):
        nhs = two_plus_one
        sub = {1: nhs[1], 2: nhs[2]}
        wait_for_leader(sub)
        retry(lambda: nhs[1].sync_request_add_witness(1, 3, W_ADDRS[3]))
        nhs[3].start_replica({}, True, KVStore, w_config(3, is_witness=True))
        # kill BOTH voters: the witness alone must never become leader
        nhs[1].close()
        nhs[2].close()
        time.sleep(1.0)
        lid, ok = nhs[3].get_leader_id(1)
        node = nhs[3]._nodes[1]
        assert not node.peer.is_leader()


class TestNonVoting:
    def test_non_voting_gets_data_but_no_vote(self, two_plus_one):
        nhs = two_plus_one
        sub = {1: nhs[1], 2: nhs[2]}
        wait_for_leader(sub)
        s = nhs[1].get_noop_session(1)
        propose_r(nhs[1], s, set_cmd("nv1", b"x"))
        retry(lambda: nhs[1].sync_request_add_non_voting(1, 3, W_ADDRS[3]))
        nhs[3].start_replica(
            {}, True, KVStore, w_config(3, is_non_voting=True)
        )
        propose_r(nhs[1], s, set_cmd("nv2", b"y"))

        # data DOES replicate to the non-voting replica
        def check():
            if nhs[3].stale_read(1, "nv2") != b"y":
                raise RuntimeError("non-voting replica not caught up yet")
            return True

        retry(check, deadline=15.0)
        # but it is not part of the quorum: killing voter 2 blocks commits
        nhs[2].close()
        time.sleep(0.5)
        with pytest.raises(Exception):
            nhs[1].sync_propose(s, set_cmd("nv3", b"z"), timeout=1.5)


# ---------------------------------------------------------------------------
# concurrent state machine tier
# ---------------------------------------------------------------------------
from dragonboat_tpu import IConcurrentStateMachine


class ConcurrentKV(IConcurrentStateMachine):
    """Batched-update KV with PrepareSnapshot (lock-free tier)."""

    def __init__(self, shard_id, replica_id):
        self.data = {}
        self.batches = 0
        self.prepared = 0

    def update(self, entries):
        self.batches += 1
        out = []
        for e in entries:
            op, k, v = pickle.loads(e.cmd)
            if op == "set":
                self.data[k] = v
            out.append(
                type(e)(index=e.index, cmd=e.cmd, result=Result(value=len(self.data)))
            )
        return out

    def lookup(self, query):
        return self.data.get(query)

    def prepare_snapshot(self):
        self.prepared += 1
        return dict(self.data)  # cheap point-in-time capture

    def save_snapshot(self, ctx, w, files, done):
        w.write(pickle.dumps(ctx))

    def recover_from_snapshot(self, r, files, done):
        self.data = pickle.loads(r.read())


class TestConcurrentSM:
    def test_batched_update_and_snapshot(self):
        from dragonboat_tpu.transport.inproc import reset_inproc_network
        from test_nodehost import ADDRS as NADDRS, make_nodehost, wait_for_leader

        reset_inproc_network()
        for rid in NADDRS:
            shutil.rmtree(nh_dir(rid), ignore_errors=True)
        nhs = {rid: make_nodehost(rid) for rid in NADDRS}
        try:
            for rid, nh in nhs.items():
                nh.start_replica(NADDRS, False, ConcurrentKV, od_config(rid))
            wait_for_leader(nhs)
            nh = nhs[1]
            s = nh.get_noop_session(1)
            from test_nodehost import propose_r, set_cmd

            # cut the catch-up follower off FIRST: a replica restarted on
            # a fresh logdb after acking entries is disk loss (outside
            # raft's model); the snapshot path serves replicas that fell
            # behind the compaction point
            fid = 3
            nhs[fid].close()
            for i in range(25):
                propose_r(nh, s, set_cmd(f"c-{i}", str(i).encode()))
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    if nhs[2].sync_read(1, "c-24", timeout=2.0) == b"24":
                        break
                except Exception:
                    pass
                time.sleep(0.05)
            assert nhs[2].sync_read(1, "c-24", timeout=5.0) == b"24"
            # snapshot uses PrepareSnapshot (concurrent path)
            nh.sync_request_snapshot(1, compaction_overhead=1)
            sm = nh._nodes[1].sm.managed.sm
            assert sm.prepared >= 1
            # catch-up from the snapshot still works: fresh follower
            for i in range(3):
                propose_r(nh, s, set_cmd(f"cp-{i}", b"v"))
            nhf = make_nodehost(fid)
            nhs[fid] = nhf
            nhf.start_replica(NADDRS, False, ConcurrentKV, od_config(fid))
            deadline = time.time() + 10
            while time.time() < deadline:
                if nhf.stale_read(1, "c-0") == b"0":
                    break
                time.sleep(0.05)
            assert nhf.stale_read(1, "c-0") == b"0"
        finally:
            for h in nhs.values():
                h.close()
