"""The 64-bit story: per-row index rebasing on the device path.

The host WAL keeps 64-bit log indexes (reference: raftpb uint64 indexes
[U]); the device lanes are int32.  Rather than aging long-lived rows off
the device at 2^31 (the r02 policy), the engine rebases every index
quantity by a per-row multiple of W at upload and converts back at every
boundary (messages, merges, snapshot lanes, materialize).  These tests
pin that arithmetic:

  * a row whose log lives PAST 2^31 round-trips upload -> materialize
    exactly and is stepped ON THE DEVICE (a proposal appends + commits
    at absolute indexes > 2^31);
  * the full cluster pipeline runs with nonzero bases at ordinary scale
    (every 33rd committed index flips the base, so normal workloads
    exercise the shifted encode/decode/merge paths continuously);
  * remaining int32 ceilings (terms; pathological match spread) fall
    back to the scalar path loudly, never silently corrupt.
"""
import os
import shutil
import time

import numpy as np
import pytest

from dragonboat_tpu.ops.engine import VectorStepEngine, _RowMeta
from dragonboat_tpu.pb import Entry, EntryType, Message, MessageType, Snapshot
from dragonboat_tpu.raft import InMemLogReader, Raft
from dragonboat_tpu.raft.peer import Peer
from dragonboat_tpu.raft.raft import RaftRole
from dragonboat_tpu.node import StepInputs

B31 = 2**31

GEOM = dict(capacity=4, P=5, W=32, M=8, E=4, O=32)


@pytest.fixture(scope="module")
def engine():
    return VectorStepEngine(None, **GEOM)


def high_raft(replica_id=1, peers=(1,), base_index=B31 + 100, term=3):
    """A raft whose log was compacted at a snapshot past 2^31."""
    r = Raft(
        shard_id=1,
        replica_id=replica_id,
        peers={p: f"a{p}" for p in peers},
        election_timeout=10,
        heartbeat_timeout=2,
        log_reader=InMemLogReader(),
    )
    ss = Snapshot(index=base_index, term=term,
                  membership=r.get_membership(), shard_id=1)
    r.log.logdb.apply_snapshot(ss)
    r.log.restore(ss)
    r.term = term
    return r


class _Stub:
    def __getattr__(self, name):
        return lambda *a, **kw: None


class FakeNode:
    """The minimal node surface _plan_device/_upload/_materialize/
    _device_step touch (a real Node needs the whole NodeHost wiring)."""

    def __init__(self, raft):
        self.peer = Peer(raft)
        self.shard_id = raft.shard_id
        self.replica_id = raft.replica_id
        self.stopped = False
        self.tick_count = 0
        self.notify_work = None
        # r9 update-lane surface: leader view (lane-diff notifications
        # sync it at upload), pending-table hint cell, LogDB binding
        # (no slot protocol -> the engine takes the list-form persist)
        self.leader_id = raft.leader_id
        self.pending_deadline_hint = [1 << 62]
        self.pending_tables = ()
        self.hs_lane_slot = -1
        self.logdb = None
        self.engine_apply_ready = None
        self._trace_spans = {}

        class _Reads:
            def has_pending(self):
                return False

            def peek_ctx(self):
                return None

        class _Quiesce:
            enabled = False

            def is_quiesced(self):
                return False

        class _SM:
            last_applied = 0

        class _Pending:
            def gc(self, tick):
                pass

        self.device_reads = _Reads()
        # the forwarded-read ledger of a resident follower: empty here
        self.device_forwarded_reads = {}
        self.quiesce = _Quiesce()
        self.sm = _SM()
        self.pending_proposal = self.pending_read_index = \
            self.pending_config_change = self.pending_snapshot = \
            self.pending_leader_transfer = _Pending()

    def dispatch_dropped(self, u):
        pass

    def _check_leader_change(self):
        pass

    def adopt_forwarded_reads(self):
        pass

    def stop(self):
        self.stopped = True


class TestRebaseArithmetic:
    def test_compute_base_is_w_multiple_and_bounded(self, engine):
        r = high_raft(base_index=B31 + 100)
        base = engine._compute_base(r)
        assert base % GEOM["W"] == 0
        assert 0 < base <= B31 + 100  # <= committed

    def test_fresh_log_base_is_zero(self, engine):
        r = Raft(shard_id=1, replica_id=1, peers={1: "a1"},
                 election_timeout=10, heartbeat_timeout=2,
                 log_reader=InMemLogReader())
        assert engine._compute_base(r) == 0

    def test_upload_materialize_roundtrip_past_2_31(self, engine):
        r = high_raft(replica_id=1, peers=(1, 2, 3))
        # remote lanes are live state only on leaders (followers' stale
        # lanes deliberately clamp to the sentinel)
        r.role = RaftRole.LEADER
        r.leader_id = 1
        # what become_leader/_append_one maintain on a real leader
        r.remotes[1].match = B31 + 100
        r.remotes[1].next = B31 + 101
        r.remotes[2].match = B31 + 80
        r.remotes[2].next = B31 + 101
        r.remotes[3].match = 0          # fresh peer: sentinel survives
        r.remotes[3].next = B31 + 101
        node = FakeNode(r)
        with engine._lock:
            g = engine._attach(node)
            engine._base[g] = engine._compute_base(r)
            engine._upload_rows([(g, r)])
            committed0 = r.log.committed
            # scribble, then materialize back from the device
            r.log.committed = 0
            r.remotes[2].match = 0
            engine._meta[g].dirty = True
            engine._materialize_rows([g])
        assert r.log.committed == committed0 > B31
        assert r.remotes[2].match == B31 + 80
        assert r.remotes[2].next == B31 + 101
        assert r.remotes[3].match == 0
        assert not node.stopped
        engine.detach(node.shard_id)

    def test_device_step_appends_past_2_31(self, engine):
        """A single-voter row at absolute index > 2^31 is stepped ON THE
        DEVICE: ticks elect it, a proposal appends and commits — all in
        rebased int32 lanes, merged back to 64-bit host indexes."""
        r = high_raft(replica_id=1, peers=(1,), base_index=B31 + 100)
        node = FakeNode(r)
        with engine._lock:
            g = engine._attach(node)
            si = StepInputs(ticks=1)
            plan = engine._plan_device(node, si, False, g)
            assert plan is not None, "high-index row must stay device-eligible"
            assert engine._base[g] > 0
            engine._upload_rows([(g, r)])
            # elections need the randomized timeout: tick until leader
            for _ in range(40):
                if r.role == RaftRole.LEADER:
                    break
                si = StepInputs(ticks=1)
                plan = engine._plan_device(node, si, False, g)
                engine._device_step([(node, g, si, plan)])
            assert r.role == RaftRole.LEADER
            barrier = r.log.last_index()
            assert barrier == B31 + 101  # the become-leader barrier
            assert r.log.committed == barrier
            # a proposal at the high window
            ent = Entry(type=EntryType.APPLICATION, cmd=b"hello")
            si = StepInputs(proposals=[ent])
            plan = engine._plan_device(node, si, False, g)
            assert plan is not None
            engine._device_step([(node, g, si, plan)])
            assert r.log.last_index() == B31 + 102
            assert r.log.committed == B31 + 102
            got = r.log._get_entries(B31 + 102, B31 + 103, 2**62)
            assert got[0].cmd == b"hello"
        engine.detach(node.shard_id)

    def test_reject_hint_below_base_takes_host_path(self, engine):
        """A follower whose last index sits below the leader's base
        rejects a probe with a sub-base hint; the kernel's decrease
        floor can't walk next under the base, so the plan must punt the
        row to the scalar path (which decreases in absolute space) —
        the stall found in review."""
        r = high_raft(replica_id=1, peers=(1, 2), base_index=B31 + 100)
        r.role = RaftRole.LEADER
        r.leader_id = 1
        r.remotes[1].match = B31 + 100
        r.remotes[1].next = B31 + 101
        r.remotes[2].match = 0            # fresh view of the peer
        r.remotes[2].next = B31 + 101
        node = FakeNode(r)
        with engine._lock:
            g = engine._attach(node)
            reject = Message(
                type=MessageType.REPLICATE_RESP, from_=2, to=1, shard_id=1,
                term=r.term, reject=True,
                log_index=B31 + 100,      # the probed prev
                hint=500,                 # follower's last: below base
                commit=500,               # realistic: commit <= last
            )
            plan = engine._plan_device(
                node, StepInputs(received=[reject]), False, g
            )
            assert plan is None
            # a same-window (>= base) reject hint stays device-eligible
            ok = Message(
                type=MessageType.REPLICATE_RESP, from_=2, to=1, shard_id=1,
                term=r.term, reject=True,
                log_index=B31 + 100,
                hint=B31 + 99,
                commit=B31 + 99,
            )
            plan = engine._plan_device(
                node, StepInputs(received=[ok]), False, g
            )
            assert plan is not None
        engine.detach(node.shard_id)

    def test_wide_match_spread_falls_back_loudly(self, engine):
        """A LEADER with a peer stuck at a tiny positive match while
        last_index is past 2^31 has a >int32 rebased window — the row
        must stay on the scalar path (no silent wrap)."""
        r = high_raft(replica_id=1, peers=(1, 2))
        r.role = RaftRole.LEADER
        r.leader_id = 1
        r.remotes[1].match = B31 + 100
        r.remotes[1].next = B31 + 101
        r.remotes[2].match = 5  # pathological: 2^31 spread
        r.remotes[2].next = 6
        node = FakeNode(r)
        with engine._lock:
            g = engine._attach(node)
            plan = engine._plan_device(node, StepInputs(ticks=1), False, g)
        assert plan is None
        engine.detach(node.shard_id)


class ReadFakeNode(FakeNode):
    """FakeNode that records the engine's intercepted synthetic
    READ_INDEX_RESP-to-self messages (the device-read contract)."""

    def __init__(self, raft):
        super().__init__(raft)
        self.read_resps = []

    def handle_device_read_resp(self, m):
        self.read_resps.append(m)


class TestDeviceReadsWithBase:
    """Device-path linearizable reads on a RESIDENT LEADER whose row
    base is nonzero — the advisor-found stall: the kernel's synthetic
    READ_INDEX_RESP overloads log_index as a voter id (or the 0
    "recorded" marker), so the rebase shift must never touch it, while
    its commit field IS an index and must shift to absolute."""

    def test_single_voter_read_served_past_2_31(self, engine):
        from dragonboat_tpu.pb import SystemCtx

        r = high_raft(replica_id=1, peers=(1,), base_index=B31 + 100)
        node = ReadFakeNode(r)
        with engine._lock:
            g = engine._attach(node)
            si = StepInputs(ticks=1)
            plan = engine._plan_device(node, si, False, g)
            engine._upload_rows([(g, r)])
            for _ in range(40):
                if r.role == RaftRole.LEADER:
                    break
                si = StepInputs(ticks=1)
                plan = engine._plan_device(node, si, False, g)
                engine._device_step([(node, g, si, plan)])
            assert r.role == RaftRole.LEADER
            assert engine._base[g] > 0
            ctx = SystemCtx(low=7, high=9)
            si = StepInputs(read_indexes=[ctx])
            plan = engine._plan_device(node, si, True, g)
            assert plan is not None, "leader reads must stay on device"
            engine._device_step([(node, g, si, plan)])
        assert node.read_resps, "no synthetic read resp intercepted"
        m = node.read_resps[-1]
        assert not m.reject
        # the "request recorded" marker must survive the rebase shift
        assert m.log_index == 0
        # ...while the recorded read index converts to ABSOLUTE
        assert m.commit == r.log.committed > B31
        assert (m.hint, m.hint_high) == (7, 9)
        engine.detach(node.shard_id)

    def test_voter_confirmations_not_shifted(self, engine):
        """3-voter leader at base > 0: the READ_INDEX broadcast rides
        heartbeats; each HEARTBEAT_RESP echoing the ctx surfaces as a
        READ_INDEX_RESP whose log_index is the VOTER ID — with the
        shift bug it came back as id+base and quorum never confirmed.

        Base is MODEST here (the common steady state: committed >= W):
        peer resps carry log_index=0, so a base past 2^31 pushes them
        outside the int32 lane bound and the row (correctly, loudly)
        bounces to the scalar path instead."""
        from dragonboat_tpu.pb import SystemCtx

        base0 = 6400
        r = high_raft(replica_id=1, peers=(1, 2, 3), base_index=base0)
        node = ReadFakeNode(r)
        with engine._lock:
            g = engine._attach(node)
            si = StepInputs(ticks=1)
            plan = engine._plan_device(node, si, False, g)
            assert plan is not None
            engine._upload_rows([(g, r)])
            # drive a device election: ticks until the campaign fires,
            # then grant votes from both peers
            for _ in range(40):
                if r.role == RaftRole.CANDIDATE:
                    break
                si = StepInputs(ticks=1)
                plan = engine._plan_device(node, si, False, g)
                engine._device_step([(node, g, si, plan)])
            assert r.role == RaftRole.CANDIDATE
            votes = [
                Message(type=MessageType.REQUEST_VOTE_RESP, from_=p, to=1,
                        shard_id=1, term=r.term, commit=base0)
                for p in (2, 3)
            ]
            si = StepInputs(received=votes)
            plan = engine._plan_device(node, si, False, g)
            assert plan is not None
            engine._device_step([(node, g, si, plan)])
            assert r.role == RaftRole.LEADER
            barrier = r.log.last_index()
            # commit the barrier: quorum ack from voter 2
            ack = Message(type=MessageType.REPLICATE_RESP, from_=2, to=1,
                          shard_id=1, term=r.term, log_index=barrier,
                          commit=base0)
            si = StepInputs(received=[ack])
            plan = engine._plan_device(node, si, False, g)
            assert plan is not None
            engine._device_step([(node, g, si, plan)])
            assert r.log.committed == barrier > base0
            # the read: recorded marker first...
            ctx = SystemCtx(low=11, high=13)
            si = StepInputs(read_indexes=[ctx])
            plan = engine._plan_device(node, si, True, g)
            assert plan is not None
            engine._device_step([(node, g, si, plan)])
            assert node.read_resps
            rec = node.read_resps[-1]
            assert not rec.reject and rec.log_index == 0
            assert rec.commit == barrier
            # ...then a ctx-echoing heartbeat resp from voter 2
            hb = Message(type=MessageType.HEARTBEAT_RESP, from_=2, to=1,
                         shard_id=1, term=r.term, hint=11, hint_high=13,
                         commit=base0)
            si = StepInputs(received=[hb])
            plan = engine._plan_device(node, si, False, g)
            assert plan is not None
            engine._device_step([(node, g, si, plan)])
        confirms = [m for m in node.read_resps
                    if not m.reject and m.log_index != 0]
        assert confirms, "voter confirmation never surfaced"
        assert confirms[-1].log_index == 2  # the voter id, NOT id+base
        assert (confirms[-1].hint, confirms[-1].hint_high) == (11, 13)
        engine.detach(node.shard_id)


class TestClusterRebasing:
    def test_pipeline_runs_with_nonzero_bases(self):
        """Ordinary cluster workload past W entries: re-uploads compute
        nonzero bases, so the shifted encode/decode/merge paths carry
        real traffic (not just the unit arithmetic above)."""
        import sys

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_nodehost import ADDRS, KVStore, propose_r, set_cmd, \
            wait_for_leader
        from test_vector_engine import make_vector_nodehost, read_r, \
            vec_shard_config
        from dragonboat_tpu.transport.inproc import reset_inproc_network

        reset_inproc_network()
        for rid in ADDRS:
            shutil.rmtree(f"/tmp/nh-vec-{rid}", ignore_errors=True)
        nhs = {rid: make_vector_nodehost(rid) for rid in ADDRS}
        try:
            for rid, nh in nhs.items():
                nh.start_replica(ADDRS, False, KVStore, vec_shard_config(rid))
            wait_for_leader(nhs)
            s = nhs[1].get_noop_session(1)
            # push the log well past W (32), with periodic cold
            # excursions so rows re-upload and recompute bases.  A
            # follower read is no excursion: its forwarded ReadIndex is
            # stepped on the device at both ends.  A transfer request a
            # follower forwards over the wire is cold input: its row
            # leaves the device for the host path and uploads again
            for i in range(80):
                propose_r(nhs[1], s, set_cmd(f"k{i}", str(i).encode()))
                if i % 20 == 19:
                    lead = wait_for_leader(nhs)
                    follower = next(r for r in ADDRS if r != lead)
                    nhs[follower].request_leader_transfer(1, lead)
                    assert read_r(nhs[1 + i % 3], 1, f"k{i}") == \
                        str(i).encode()
            rebased = []
            for rid, nh in nhs.items():
                eng = nh.engine.step_engine
                with eng._lock:
                    rebased.extend(int(b) for b in eng._base if b > 0)
            assert rebased, "no row ever ran with a nonzero base"
            assert all(b % 32 == 0 for b in rebased)
            for rid in ADDRS:
                assert read_r(nhs[rid], 1, "k79") == b"79"
        finally:
            for nh in nhs.values():
                nh.close()
