"""Chaos over the COLOCATED engine: the product device path under
partitions, kills, restarts and entry-cache eviction pressure.

reference: the drummer/monkeytest methodology [U], applied per VERDICT
r3 next-#7 to the colocated stack (r3 chaos ran only the host scalar
engine).  Same invariants as tests/test_chaos.py:

  I1 (no loss):      every ACKED write is present after healing
  I2 (agreement):    all replicas' SM state is identical after settling
  I3 (availability): the cluster accepts writes again after healing

plus the colocated-specific ones:

  I4 (device path):  consensus actually routes on device (routed
                     deliveries > 0) — a chaos pass that silently fell
                     back to the host path would prove nothing
  I5 (no fail-stop): divergence fail-stops are for REAL divergence;
                     partitions, restarts and cache eviction churn must
                     not trigger one (divergence_halts == 0)

Partitions are injected at BOTH layers a colocated cluster talks
through: ``ColocatedVectorEngine.set_partition`` severs the device
routes (cross-group messages fall to the host transport) and the
in-proc transport drop hook loses them there — both sides keep ticking
and campaigning, exactly a network partition.
"""
import os
import random
import shutil
import threading
import time

import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    Fault,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.storage.tan import tan_logdb_factory

from test_chaos import Cluster, chaos_client
from test_nodehost import KVStore, set_cmd, wait_for_leader

ADDRS = {1: "colo-chaos-1", 2: "colo-chaos-2", 3: "colo-chaos-3"}

# small ring window so eviction pressure is reachable in test time:
# entry cache depth is max(8*W, 8*M*E) = 256 entries per shard
GEOM = dict(capacity=16, P=5, W=8, M=8, E=4, O=32, budget=4)


def colo_chaos_config(replica_id, shard_id=1):
    return Config(
        replica_id=replica_id,
        shard_id=shard_id,
        election_rtt=20,
        heartbeat_rtt=2,
        pre_vote=True,
        check_quorum=True,
        snapshot_entries=0,
    )


class ColocatedCluster(Cluster):
    """The chaos Cluster over one shared ColocatedEngineGroup."""

    ADDRS = ADDRS

    def __init__(self, seed=0):
        self.group = ColocatedEngineGroup(**GEOM)
        super().__init__(seed=seed)

    def _dir(self, rid):
        # a directory a process: test_updatelanes.py builds this cluster
        # too, and under xdist the two files can run at the same time
        return f"/tmp/nh-cchaos-{os.getpid()}-{rid}"

    def close(self):
        super().close()
        for rid in self.ADDRS:
            shutil.rmtree(self._dir(rid), ignore_errors=True)

    def config(self, rid):
        return colo_chaos_config(rid)

    def make_nodehost(self, rid):
        return NodeHost(
            NodeHostConfig(
                nodehost_dir=self._dir(rid),
                rtt_millisecond=5,
                raft_address=self.ADDRS[rid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    logdb_factory=tan_logdb_factory,
                    step_engine_factory=self.group.factory,
                ),
            )
        )

    def partition(self, side_a):
        super().partition(side_a)  # transport drop hooks
        side = {int(r) for r in side_a}
        core = self.group.core
        if core is not None:
            # member rid hosts replica rid of every shard in this harness
            core.set_partition(lambda s, r: 1 if r in side else 0)

    def heal(self):
        super().heal()
        core = self.group.core
        if core is not None:
            core.set_partition(None)

    def stats(self):
        core = self.group.core
        return dict(core.stats) if core is not None else {}


class TestColocatedChaos:
    def test_partitions_and_restarts_preserve_acked_writes(self):
        cluster = ColocatedCluster()
        acked = {}
        stop = threading.Event()
        threads = [
            threading.Thread(
                target=chaos_client, args=(cluster, acked, stop, f"c{i}"),
                daemon=True,
            )
            for i in range(2)
        ]
        try:
            wait_for_leader(cluster.nhs)
            for t in threads:
                t.start()
            rng = random.Random(11)
            for i in range(6):
                fault = rng.randrange(3)
                if fault == 0:
                    side = rng.sample(list(cluster.ADDRS), rng.choice([1, 2]))
                    cluster.partition(side)
                    time.sleep(rng.uniform(0.8, 1.5))
                    cluster.heal()
                elif fault == 1 and len(cluster.nhs) == 3:
                    rid = rng.choice(list(cluster.nhs))
                    cluster.kill(rid)
                    time.sleep(rng.uniform(0.5, 1.0))
                    cluster.restart(rid)
                else:
                    time.sleep(rng.uniform(0.5, 1.0))
                time.sleep(0.5)
            stop.set()
            for t in threads:
                t.join(timeout=5)
            assert len(acked) > 10, "clients made no progress"
            cluster.settle_and_check_agreement(acked, timeout=60.0)
            st = cluster.stats()
            assert st.get("routed_delivered", 0) > 0, st  # I4
            assert st.get("divergence_halts", 0) == 0, st  # I5
        finally:
            stop.set()
            cluster.close()

    @pytest.mark.flaky_isolated
    def test_forced_kernel_escalations_under_load(self):
        """Nemesis-forced device-kernel escalations: rows are randomly
        bounced through the escalation recovery machinery (discard
        device effects / scalar replay / re-upload) while clients
        propose.  The cluster must keep agreeing with zero divergence
        fail-stops — escalation is a recovery path, not a fault."""
        cluster = ColocatedCluster(seed=17)
        acked = {}
        stop = threading.Event()
        t = threading.Thread(
            target=chaos_client, args=(cluster, acked, stop, "esc"),
            daemon=True,
        )
        try:
            wait_for_leader(cluster.nhs)
            cluster.nemesis.install_engine(cluster.group.core)
            # p is modest: each forced escalation costs a materialize +
            # scalar replay + a several-step scalar hold, so a high rate
            # legitimately throttles the shard rather than proving
            # anything about divergence
            f = cluster.nemesis.activate(
                Fault("escalate", targets=(1,), p=0.08)
            )
            t.start()
            time.sleep(4.0)
            cluster.nemesis.deactivate(f)
            stop.set()
            t.join(timeout=5)
            assert len(acked) > 5, "no progress under forced escalations"
            assert cluster.nemesis.stats.get("engine_escalations", 0) > 0
            cluster.settle_and_check_agreement(acked, timeout=60.0)
            st = cluster.stats()
            assert st.get("divergence_halts", 0) == 0, st  # I5
        finally:
            stop.set()
            cluster.close()

    def test_entry_cache_eviction_pressure(self):
        """Slow follower + append storm past the cache depth (VERDICT r3
        weak-#8): partition one member out, commit past the per-shard
        entry-cache depth (256 here), heal, and require full catch-up
        with ZERO fail-stops — stale appends must fall to the host path
        (ring_ok / route tables), never fabricate entries or halt the
        replica."""
        cluster = ColocatedCluster()
        acked = {}
        try:
            wait_for_leader(cluster.nhs)
            cluster.partition([3])
            # storm: past the 256-entry cache depth while rid 3 is deaf
            majority = [1, 2]
            done = 0
            deadline = time.time() + 150.0
            while done < 300 and time.time() < deadline:
                rid = majority[done % 2]
                try:
                    nh = cluster.nhs[rid]
                    s = nh.get_noop_session(1)
                    key = f"storm-{done}"
                    val = f"v{done}".encode()
                    nh.sync_propose(s, set_cmd(key, val), timeout=5.0)
                    acked[key] = val
                    done += 1
                except Exception:
                    time.sleep(0.05)
            assert done >= 300, f"storm stalled at {done}"
            cluster.heal()
            # catch-up runs at <= E entries per wire round trip once the
            # follower is below the leader's ring; 300 entries of lag
            # needs a generous settle on a loaded CPU
            cluster.settle_and_check_agreement(acked, timeout=240.0)
            st = cluster.stats()
            assert st.get("divergence_halts", 0) == 0, st  # I5
            assert st.get("routed_delivered", 0) > 0, st  # I4
        finally:
            cluster.close()


@pytest.mark.skipif(
    not os.environ.get("CHAOS_ROUNDS"),
    reason="set CHAOS_ROUNDS=N for the long colocated schedule",
)
def test_extended_colocated_chaos_schedule():
    """The drummer-style long soak over the colocated stack."""
    rounds = int(os.environ["CHAOS_ROUNDS"])
    cluster = ColocatedCluster()
    acked = {}
    stop = threading.Event()
    threads = [
        threading.Thread(
            target=chaos_client, args=(cluster, acked, stop, f"x{i}"),
            daemon=True,
        )
        for i in range(3)
    ]
    try:
        wait_for_leader(cluster.nhs)
        for t in threads:
            t.start()
        rng = random.Random(7)
        for i in range(rounds):
            fault = rng.randrange(4)
            if fault == 0:
                side = rng.sample(list(cluster.ADDRS), rng.choice([1, 2]))
                cluster.partition(side)
                time.sleep(rng.uniform(0.5, 2.0))
                cluster.heal()
            elif fault == 1:
                rid = rng.choice(list(cluster.nhs))
                if len(cluster.nhs) > 2:
                    cluster.kill(rid)
                    time.sleep(rng.uniform(0.5, 1.5))
                    cluster.restart(rid)
            elif fault == 2:
                rid = rng.choice(list(cluster.nhs))
                f = cluster.nemesis.activate(Fault("fsync_err", targets=(rid,)))
                time.sleep(rng.uniform(0.3, 1.0))
                cluster.nemesis.deactivate(f)
            else:
                time.sleep(rng.uniform(0.5, 1.5))
            if i and i % 25 == 0:
                print(f"round {i}/{rounds} acked={len(acked)} "
                      f"stats={cluster.stats()}", flush=True)
            time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert len(acked) > rounds, "clients made no progress"
        cluster.settle_and_check_agreement(acked, timeout=120.0)
        st = cluster.stats()
        assert st.get("routed_delivered", 0) > 0, st
        assert st.get("divergence_halts", 0) == 0, st
        print("FINAL", len(acked), st, flush=True)
    finally:
        stop.set()
        cluster.close()


class TestWalFaultQuarantine:
    def test_wal_fault_quarantines_then_recovers(self):
        """A member whose WAL save fails must stop participating from
        the DEVICE path (its routed acks could outrun persistence) and
        fall back to the scalar save-before-send path until a save
        succeeds — then rejoin with no acked-write loss or divergence
        (review finding on the save-retry machinery)."""
        cluster = ColocatedCluster()
        acked = {}
        try:
            wait_for_leader(cluster.nhs)
            s1 = cluster.nhs[1].get_noop_session(1)
            cluster.nhs[1].sync_propose(s1, set_cmd("pre", b"0"), timeout=5.0)
            acked["pre"] = b"0"

            # inject a WAL fault at member 2 under proposal load
            wal_fault = cluster.nemesis.activate(
                Fault("fsync_err", targets=(2,))
            )
            done = 0
            deadline = time.time() + 60.0
            while done < 30 and time.time() < deadline:
                try:
                    key = f"w{done}"
                    cluster.nhs[1].sync_propose(
                        s1, set_cmd(key, b"x"), timeout=5.0
                    )
                    acked[key] = b"x"
                    done += 1
                except Exception:
                    time.sleep(0.05)
            assert done >= 30, f"stalled at {done} under member-2 WAL fault"
            st = cluster.stats()
            assert st.get("save_failures", 0) > 0, st

            cluster.nemesis.deactivate(wal_fault)  # disk heals
            cluster.settle_and_check_agreement(acked, timeout=120.0)
            st = cluster.stats()
            assert st.get("divergence_halts", 0) == 0, st
            # quarantine must have RELEASED: member 2's node is allowed
            # back on the device path after a successful save
            core = cluster.group.core
            n2 = cluster.nhs[2]._nodes[1]
            deadline = time.time() + 30.0
            while time.time() < deadline and n2 in core._save_quarantine:
                time.sleep(0.2)
            assert n2 not in core._save_quarantine
        finally:
            cluster.close()
