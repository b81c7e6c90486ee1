"""Chaos tests: partitions, kills, restarts under concurrent client load.

reference: the drummer/monkeytest methodology [U] — long-running
multi-NodeHost clusters with fault injection and invariant checks:

  I1 (no loss):      every ACKED write is present after healing
  I2 (agreement):    all replicas' SM state is identical after settling
  I3 (availability): the cluster accepts writes again after healing

All faults flow through the unified seeded nemesis
(dragonboat_tpu.faults.FaultController): partitions/drops on the wire
plane, fsync faults on the storage plane, plus real NodeHost
close/reopen over tan WAL dirs (kills) via the crash handlers.
"""
import os
import pickle
import random
import shutil
import threading
import time

import pytest

from dragonboat_tpu import (
    EngineConfig,
    ExpertConfig,
    Fault,
    FaultController,
    NodeHost,
    NodeHostConfig,
    RequestDropped,
    SystemBusy,
    TimeoutError_,
)
from dragonboat_tpu.storage.tan import tan_logdb_factory
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import KVStore, set_cmd, shard_config, wait_for_leader

ADDRS = {1: "cnh-1", 2: "cnh-2", 3: "cnh-3"}


def chaos_dir(replica_id):
    # a directory a process: test_chaos_extended.py and test_faults.py
    # build this cluster too, and under xdist the files can run at the
    # same time (a restart then met another worker's flock)
    return f"/tmp/nh-chaos-{os.getpid()}-{replica_id}"


def make_chaos_nodehost(replica_id):
    cfg = NodeHostConfig(
        nodehost_dir=chaos_dir(replica_id),
        rtt_millisecond=2,
        raft_address=ADDRS[replica_id],
        expert=ExpertConfig(
            engine=EngineConfig(exec_shards=2, apply_shards=2),
            logdb_factory=tan_logdb_factory,
        ),
    )
    return NodeHost(cfg)


class Cluster:
    ADDRS = ADDRS

    def __init__(self, seed=0):
        reset_inproc_network()
        self.nemesis = FaultController(seed=seed)
        self.nemesis.set_crash_handlers(self.kill, self.restart)
        for rid in self.ADDRS:
            shutil.rmtree(self._dir(rid), ignore_errors=True)
        self.nhs = {}
        for rid in self.ADDRS:
            self.start(rid)
        for rid, nh in self.nhs.items():
            nh.start_replica(self.ADDRS, False, KVStore, self.config(rid))

    def config(self, rid):
        return shard_config(rid)

    def _dir(self, rid):
        return chaos_dir(rid)

    def start(self, rid):
        self.nhs[rid] = self.make_nodehost(rid)
        self.nemesis.install_nodehost(rid, self.nhs[rid])

    def make_nodehost(self, rid):
        return make_chaos_nodehost(rid)

    def kill(self, rid):
        """Hard-ish kill: close the nodehost (tan WAL survives)."""
        self.nhs.pop(rid).close()

    def restart(self, rid):
        self.start(rid)
        self.nhs[rid].start_replica(self.ADDRS, False, KVStore, self.config(rid))

    def partition(self, side_a):
        """Messages between side_a and the rest are dropped, both ways."""
        self.nemesis.set_partition({self.ADDRS[r] for r in side_a})

    def heal(self):
        self.nemesis.heal_wire()

    def close(self):
        self.nemesis.stop()
        for nh in self.nhs.values():
            nh.close()
        self.nhs = {}

    def settle_and_check_agreement(self, acked, timeout=20.0):
        """I1 + I2: wait until every replica's SM holds all acked writes
        and all replicas agree byte-for-byte."""
        deadline = time.time() + timeout
        # nudge the shard so followers catch up
        while time.time() < deadline:
            datas = []
            for nh in self.nhs.values():
                node = nh._nodes.get(1)
                sm = node.sm.managed.sm  # the user KVStore
                datas.append(dict(sm.data))
            ok = all(d == datas[0] for d in datas)
            missing = [k for k in acked if acked[k] != datas[0].get(k)]
            if ok and not missing:
                return datas[0]
            time.sleep(0.1)
        raise AssertionError(
            f"no agreement: sizes={[len(d) for d in datas]} "
            f"missing_acked={len(missing)} sample={missing[:5]}"
        )


def chaos_client(cluster, acked, stop, tag):
    """Proposes continuously via random replicas; records ACKs."""
    i = 0
    while not stop.is_set():
        i += 1
        key = f"{tag}-{i}"
        val = f"{tag}v{i}".encode()
        rids = list(cluster.nhs)
        rid = random.choice(rids)
        try:
            nh = cluster.nhs.get(rid)
            if nh is None:
                continue
            s = nh.get_noop_session(1)
            nh.sync_propose(s, set_cmd(key, val), timeout=1.0)
            acked[key] = val  # ONLY acked writes must survive
        except (TimeoutError_, RequestDropped, SystemBusy, Exception):
            pass
        time.sleep(0.002)


class TestChaos:
    def test_partitions_and_restarts_preserve_acked_writes(self):
        random.seed(7)
        cluster = Cluster()
        acked = {}
        stop = threading.Event()
        clients = [
            threading.Thread(
                target=chaos_client, args=(cluster, acked, stop, f"c{k}")
            )
            for k in range(3)
        ]
        try:
            wait_for_leader(cluster.nhs)
            for t in clients:
                t.start()
            # fault schedule: partitions + a kill/restart cycle
            for round_ in range(4):
                time.sleep(0.8)
                minority = [random.choice(list(ADDRS))]
                cluster.partition(minority)
                time.sleep(0.8)
                cluster.heal()
                time.sleep(0.4)
                victim = random.choice(list(ADDRS))
                cluster.kill(victim)
                time.sleep(0.6)
                cluster.restart(victim)
                wait_for_leader(cluster.nhs, timeout=20.0)
            stop.set()
            for t in clients:
                t.join(timeout=5.0)
            cluster.heal()
            assert len(acked) > 20, f"chaos made no progress: {len(acked)}"
            final = cluster.settle_and_check_agreement(acked)
            # I3: cluster is still writable
            wait_for_leader(cluster.nhs, timeout=10.0)
            nh = next(iter(cluster.nhs.values()))
            s = nh.get_noop_session(1)
            deadline = time.time() + 10.0
            while True:
                try:
                    nh.sync_propose(s, set_cmd("final", b"1"), timeout=1.0)
                    break
                except Exception:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.05)
        finally:
            stop.set()
            for t in clients:
                t.join(timeout=5.0)
            cluster.close()

    def test_majority_partition_keeps_committing(self):
        random.seed(11)
        cluster = Cluster()
        try:
            wait_for_leader(cluster.nhs)
            # isolate replica 3: the {1,2} majority must keep working
            cluster.partition([3])
            acked = {}
            nh = cluster.nhs[1]
            s = nh.get_noop_session(1)
            deadline = time.time() + 15.0
            n_ok = 0
            while n_ok < 10 and time.time() < deadline:
                try:
                    key = f"maj-{n_ok}"
                    nh.sync_propose(s, set_cmd(key, b"v"), timeout=1.0)
                    acked[key] = b"v"
                    n_ok += 1
                except Exception:
                    time.sleep(0.05)
            assert n_ok == 10, f"majority only committed {n_ok}"
            cluster.heal()
            cluster.settle_and_check_agreement(acked)
        finally:
            cluster.close()

    def test_lossy_delaying_duplicating_reordering_network(self):
        """Wire faults beyond what the old drop-only hook could express:
        probabilistic loss + delay + duplication + reordering on every
        lane at once.  Raft's idempotent message handling must keep the
        cluster committing with no acked-write loss (I1/I2/I3)."""
        cluster = Cluster(seed=29)
        acked = {}
        stop = threading.Event()
        clients = [
            threading.Thread(
                target=chaos_client, args=(cluster, acked, stop, f"n{k}"),
                daemon=True,
            )
            for k in range(2)
        ]
        try:
            wait_for_leader(cluster.nhs)
            addrs = tuple(ADDRS.values())
            n = cluster.nemesis
            n.activate(Fault("drop", targets=addrs, p=0.05))
            n.activate(Fault("delay", targets=addrs, p=0.2, delay=0.005))
            n.activate(Fault("duplicate", targets=addrs, p=0.25))
            n.activate(Fault("reorder", targets=addrs, p=0.25))
            for t in clients:
                t.start()
            time.sleep(3.0)
            stop.set()
            for t in clients:
                t.join(timeout=5.0)
            n.heal_all()
            assert len(acked) > 20, f"no progress under lossy net: {len(acked)}"
            assert n.stats.get("wire_duplicated", 0) > 0, n.stats
            assert n.stats.get("wire_reordered", 0) > 0, n.stats
            cluster.settle_and_check_agreement(acked)
        finally:
            stop.set()
            cluster.close()

    def test_minority_partition_cannot_commit(self):
        cluster = Cluster()
        try:
            lid = wait_for_leader(cluster.nhs)
            # isolate the LEADER alone: it must not be able to commit
            cluster.partition([lid])
            time.sleep(0.3)  # let the old leader notice nothing acks
            nh = cluster.nhs[lid]
            s = nh.get_noop_session(1)
            with pytest.raises(Exception):
                nh.sync_propose(s, set_cmd("stale", b"x"), timeout=1.5)
            cluster.heal()
            # after healing the write never appears (it was never committed
            # by a quorum; the new term's log wins)
            cluster.settle_and_check_agreement({})
        finally:
            cluster.close()


# ---------------------------------------------------------------------------
# chaos over real TCP sockets + tan WAL (the config-5 transport stack)
# ---------------------------------------------------------------------------
from dragonboat_tpu.transport.tcp import tcp_transport_factory

TCP_CHAOS_ADDRS = {1: "127.0.0.1:27601", 2: "127.0.0.1:27602", 3: "127.0.0.1:27603"}


class TcpCluster(Cluster):
    ADDRS = TCP_CHAOS_ADDRS

    def _dir(self, rid):
        return f"/tmp/nh-tchaos-{rid}"

    def make_nodehost(self, rid):
        return NodeHost(
            NodeHostConfig(
                nodehost_dir=self._dir(rid),
                rtt_millisecond=2,
                raft_address=self.ADDRS[rid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=2, apply_shards=2),
                    logdb_factory=tan_logdb_factory,
                    transport_factory=tcp_transport_factory,
                ),
            )
        )


class TestChaosTCP:
    def test_partitions_and_restarts_over_tcp_tan(self):
        random.seed(23)
        cluster = TcpCluster()
        acked = {}
        stop = threading.Event()
        clients = [
            threading.Thread(
                target=chaos_client, args=(cluster, acked, stop, f"t{k}")
            )
            for k in range(3)
        ]
        try:
            wait_for_leader(cluster.nhs)
            for t in clients:
                t.start()
            for round_ in range(3):
                time.sleep(0.8)
                cluster.partition([random.choice(list(TCP_CHAOS_ADDRS))])
                time.sleep(0.8)
                cluster.heal()
                time.sleep(0.4)
                victim = random.choice(list(TCP_CHAOS_ADDRS))
                cluster.kill(victim)
                time.sleep(0.6)
                cluster.restart(victim)
                wait_for_leader(cluster.nhs, timeout=20.0)
            stop.set()
            for t in clients:
                t.join(timeout=5.0)
            cluster.heal()
            assert len(acked) > 15, f"no progress: {len(acked)}"
            cluster.settle_and_check_agreement(acked)
            # I3: still writable after the chaos schedule
            wait_for_leader(cluster.nhs, timeout=10.0)
            nh = next(iter(cluster.nhs.values()))
            s = nh.get_noop_session(1)
            deadline = time.time() + 10.0
            while True:
                try:
                    nh.sync_propose(s, set_cmd("tcp-final", b"1"), timeout=1.0)
                    break
                except Exception:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.05)
        finally:
            stop.set()
            for t in clients:
                t.join(timeout=5.0)
            cluster.close()


class TestPendingKeyIncarnations:
    def test_restart_allocates_disjoint_proposal_keys(self):
        """Regression for acked-write loss found by the chaos suite: a
        restarted replica re-applies its log, and old entries whose keys
        collided with freshly allocated ones completed NEW futures — a
        false ack for proposals that never committed.  Key ranges must be
        random per incarnation (reference: random key generator seed [U])."""
        reset_inproc_network()
        shutil.rmtree(chaos_dir(1), ignore_errors=True)
        keys = set()
        for _ in range(3):
            nh = make_chaos_nodehost(1)
            nh.start_replica(
                {1: ADDRS[1]}, False, KVStore, shard_config(1)
            )
            base = nh._nodes[1].pending_proposal._next_key
            assert base >> 48 == 1  # replica id preserved in the top bits
            assert base & ((1 << 47) - 1) != 0  # randomized low bits
            keys.add(base)
            nh.close()
            reset_inproc_network()
        assert len(keys) == 3, f"key bases repeated across restarts: {keys}"
