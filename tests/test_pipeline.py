"""Launch-pipeline fence tests: double-buffered generations under
membership churn.

The colocated engine's merge tail runs one generation behind the device
at pipeline depth 2 (ops/colocated.py).  The correctness contract
(docs/PARITY.md "Pipeline safety argument") is a FENCE: rows being
evicted, escalated or detached drain the pipeline to depth 0 before
membership mutates — mirroring the ≤1-launch detach-race argument at
any depth.  These tests drive eviction, detach, nemesis-forced
escalation, real below-ring kernel escalation and stop_shard while the
pipeline is at depth 2 and assert:

  F1 (fence):      _materialize_rows / _drain_pending_to_host only ever
                   run at depth 0 — device->scalar movement never races
                   an unmerged generation (a materialize mid-flight
                   would trip a false divergence halt or corrupt the
                   scalar mirrors);
  F2 (parity):     the hostplane parity oracle stays green on every
                   pipelined generation, checked against each
                   generation's OWN inputs, not the interleaved stream;
  F3 (futures):    zero lost or duplicated completions — every acked
                   proposal applies exactly once on every replica
                   (AuditKV apply-journal check) and no future is
                   stranded by the one-generation-behind merge.
"""
import shutil
import time

import numpy as np
import pytest

from dragonboat_tpu import (
    EngineConfig,
    ExpertConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.audit.model import AuditKV, audit_set_cmd
from dragonboat_tpu.ops import hostplane
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import ADDRS, KVStore, propose_r, set_cmd, wait_for_leader
from test_colocated import GEOM, colo_shard_config
from test_vector_engine import read_r

PIPE_GEOM = dict(GEOM, pipeline_depth=2)


@pytest.fixture(autouse=True)
def parity_oracle():
    """F2: every test in this module runs with the hostplane parity
    oracle armed; any divergence across a pipelined generation fails
    the test that caused it."""
    old = hostplane.PARITY
    hostplane.PARITY = True
    hostplane.PARITY_FAILURES.clear()
    before = hostplane.PARITY_FAILURE_COUNT
    yield
    assert hostplane.PARITY_FAILURE_COUNT == before, (
        hostplane.PARITY_FAILURES[:3]
    )
    hostplane.PARITY = old


def arm_fence_probe(core):
    """F1: wrap the device->scalar movement primitives to record any
    call made while generations are in flight.  The fence contract says
    membership mutation drains first, so a violation list stays empty
    through arbitrary churn."""
    violations = []
    orig_mat = core._materialize_rows
    orig_drain = core._drain_pending_to_host

    def mat(gs, state=None):
        if gs and core._inflight:
            violations.append(("materialize", list(gs),
                               len(core._inflight)))
        return orig_mat(gs, state)

    def drain(pairs):
        if pairs and core._inflight:
            violations.append(("drain_pending",
                               [g for _, g in pairs],
                               len(core._inflight)))
        return orig_drain(pairs)

    core._materialize_rows = mat
    core._drain_pending_to_host = drain
    return violations


def make_cluster(sm_cls, tag, shards=(1,), **engine_kw):
    reset_inproc_network()
    geom = dict(PIPE_GEOM, **engine_kw)
    group = ColocatedEngineGroup(**geom)
    nhs = {}
    for rid in ADDRS:
        d = f"/tmp/nh-pipe-{tag}-{rid}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[rid] = NodeHost(
            NodeHostConfig(
                nodehost_dir=d,
                rtt_millisecond=5,
                raft_address=ADDRS[rid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=group.factory,
                ),
            )
        )
    for shard in shards:
        for rid, nh in nhs.items():
            nh.start_replica(
                ADDRS, False, sm_cls,
                colo_shard_config(rid, shard_id=shard),
            )
    return group, nhs


def close_all(nhs):
    for nh in nhs.values():
        try:
            nh.close()
        except Exception:  # noqa: BLE001
            pass


def settle_journals(nhs, shard, keys, timeout=20.0):
    """Wait until every live replica's AuditKV journal holds every key,
    then return {rid: journal}."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        journals = {}
        for rid, nh in nhs.items():
            node = nh._nodes.get(shard)
            if node is None or node.stopped:
                continue
            journals[rid] = list(node.sm.managed.sm.journal)
        if journals and all(
            keys <= {k for _, k, _ in j} for j in journals.values()
        ):
            return journals
        time.sleep(0.05)
    raise AssertionError(
        f"journals never converged on {len(keys)} keys: "
        f"{ {r: len(j) for r, j in journals.items()} }"
    )


class TestPipelineFences:
    def test_stop_shard_and_detach_fence_exactly_once(self):
        """stop_shard of one shard's replica while another shard's
        pipeline is at depth 2: the detach fences (drain to depth 0),
        in-flight proposals all complete, and the AuditKV journals show
        every acked key applied exactly once on every replica (F3).
        A real sync floor keeps generations in flight long enough that
        the detach demonstrably drains a non-empty pipe (at floor 0 the
        opportunistic ripe pass merges them almost immediately)."""
        group, nhs = make_cluster(
            AuditKV, "stop", shards=(1, 2), sync_floor_ms=100.0
        )
        try:
            lead = wait_for_leader(nhs, shard_id=1)
            wait_for_leader(nhs, shard_id=2)
            core = group.core
            violations = arm_fence_probe(core)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            pending = []
            keys = set()
            for i in range(16):
                k = f"pre{i}"
                keys.add(k)
                pending.append(
                    (k, nh.propose(sess, audit_set_cmd(k, i), 20.0))
                )
            # membership mutation mid-pipeline: stop a replica of the
            # OTHER shard — its detach must drain shard 1's in-flight
            # generations before releasing the row.  Wait until the
            # pipe is observably non-empty (the 100 ms floor keeps each
            # generation in flight; a racy read is fine — the detach
            # re-checks under the core lock)
            fences0 = core.stats["pipeline_fences"]
            deadline = time.time() + 10.0
            while time.time() < deadline and not core._inflight:
                time.sleep(0.002)
            assert core._inflight, "pipeline never went in-flight"
            nhs[1 if lead != 1 else 2].stop_shard(2)
            for i in range(16):
                k = f"post{i}"
                keys.add(k)
                pending.append(
                    (k, nh.propose(sess, audit_set_cmd(k, i), 20.0))
                )
            for k, rs in pending:
                rs._event.wait(20.0)
                assert rs.code == 1, f"future lost for {k}: {rs.code}"
            assert core.stats["pipeline_fences"] > fences0
            assert violations == [], violations[:3]  # F1
            journals = settle_journals(nhs, 1, keys)
            assert len(journals) == 3
            for rid, j in journals.items():
                applied = [k for _, k, _ in j if k in keys]
                assert len(applied) == len(keys), (
                    f"replica {rid}: lost/duplicated applies — "
                    f"{len(applied)} entries for {len(keys)} acked keys"
                )
        finally:
            close_all(nhs)
        assert not group.core._inflight and not group.core._deferred

    def test_eviction_fence_follower_read(self):
        """A follower read (cold input -> host path -> eviction) lands
        while the pipeline runs: the eviction fences, the read returns
        the committed value, and proposals before/after all complete."""
        group, nhs = make_cluster(KVStore, "evict")
        try:
            lead = wait_for_leader(nhs)
            core = group.core
            violations = arm_fence_probe(core)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            pending = [
                nh.propose(sess, set_cmd(f"a{i}", b"1"), 20.0)
                for i in range(8)
            ]
            propose_r(nh, sess, set_cmd("probe", b"v"))
            follower = next(r for r in ADDRS if r != lead)
            ev0 = core.stats.get("evict_host_plan", 0)
            assert read_r(nhs[follower], 1, "probe") == b"v"
            pending.extend(
                nh.propose(sess, set_cmd(f"b{i}", b"1"), 20.0)
                for i in range(8)
            )
            for rs in pending:
                rs._event.wait(20.0)
                assert rs.code == 1, rs.code
            # the follower's row took a host excursion for the read
            assert core.stats.get("evict_host_plan", 0) > ev0
            assert violations == [], violations[:3]  # F1
        finally:
            close_all(nhs)

    def test_escalation_at_depth2(self):
        """Real below-ring kernel escalation (ESC_WINDOW) plus
        nemesis-forced plan-time excursions while double-buffered: the
        deferred escalation recovery (evict at depth 0 + scalar replay)
        keeps the cluster agreeing with zero divergence halts."""
        import test_chaos_colocated as tcc
        from dragonboat_tpu import Fault
        from test_nodehost import wait_for_leader as wfl

        cluster = tcc.ColocatedCluster(seed=23)
        try:
            wfl(cluster.nhs)
            core = cluster.group.core
            assert core._pipeline_depth >= 2
            violations = arm_fence_probe(core)

            def propose(i):
                for nh in cluster.nhs.values():
                    try:
                        s = nh.get_noop_session(1)
                        nh.sync_propose(
                            s, set_cmd(f"k{i}", f"v{i}".encode()),
                            timeout=5.0,
                        )
                        return
                    except Exception:  # noqa: BLE001 — next host
                        continue

            # nemesis-forced plan-time excursions under pipelined load
            cluster.nemesis.install_engine(core)
            f = cluster.nemesis.activate(
                Fault("escalate", targets=(1,), p=0.2)
            )
            for i in range(12):
                propose(i)
            cluster.nemesis.deactivate(f)
            # below-ring recovery under the pipeline: partition a
            # follower, commit past the W=8 ring window, heal — the
            # leader drives the healed follower back from its full log
            # (below-ring replicate / ESC_WINDOW machinery) while
            # generations double-buffer
            cluster.partition([3])
            for i in range(100, 120):
                propose(i)
            cluster.heal()
            for i in range(200, 210):
                propose(i)
            # deterministic escalation through the REAL deferred
            # machinery (a launch-reported ESC flag is timing-dependent
            # on CPU): inject the exact action a pipelined completion
            # records, then let the next step's fence run the
            # evict-at-depth-0 + hold recovery
            # the hold is read INSIDE the recovery, under the core
            # lock: it lasts only a few steps, and a fast machine had
            # already decayed it and re-uploaded the row by the time
            # this thread looked (a 4-in-10 flake at the seed)
            held = []
            real_apply = core._apply_escalation

            def apply_and_probe(node_, g_, si_):
                out = real_apply(node_, g_, si_)
                m = core._meta.get(g_)
                if m is not None:
                    held.append(
                        m.esc_hold > 0 or bool(core._lanes.dirty[g_])
                    )
                return out

            core._apply_escalation = apply_and_probe
            with core._lock:
                alive = np.nonzero(core._lanes.alive_mask())[0]
                assert len(alive), "no resident rows to escalate"
                g = int(alive[0])
                node = core._meta[g].node
                core._deferred.append(("esc", node, g, None))
            deadline = time.time() + 15.0
            i = 1000
            while time.time() < deadline and not (
                core.stats.get("evict_escalation", 0) > 0
            ):
                propose(i)
                i += 1
                time.sleep(0.02)
            assert core.stats.get("evict_escalation", 0) > 0, (
                "deferred escalation never ran"
            )
            assert held and all(held), (
                "escalated row not held on the scalar path"
            )
            for i in range(300, 310):
                propose(i)
            time.sleep(0.5)
            assert core.stats.get("divergence_halts", 0) == 0, core.stats
            assert violations == [], violations[:3]  # F1
        finally:
            cluster.close()

    def test_idle_drain_completes_tail_generation(self):
        """The completion guarantee: with work dried up, the last
        dispatched generation still merges (self-notify drives an idle
        call that drains the pipeline) — no future waits forever on a
        generation nobody completes."""
        group, nhs = make_cluster(KVStore, "idle")
        try:
            lead = wait_for_leader(nhs)
            # wait_for_leader returns the AGREED leader's replica id
            # (== its host key here): re-probing is_leader_of after
            # the wait raced suite-load leadership blips into a
            # StopIteration (tier-1 flake)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            for i in range(5):
                # serial sync proposals: each one's completion depends
                # on generations that must merge without a follow-up
                # workload pushing the pipeline
                propose_r(nh, sess, set_cmd(f"k{i}", b"x"))
            deadline = time.time() + 10.0
            while time.time() < deadline and group.core._inflight:
                time.sleep(0.02)
            assert not group.core._inflight, (
                "tail generation never drained"
            )
        finally:
            close_all(nhs)


class TestPipelineKnobs:
    def test_depth_and_floor_kwargs(self):
        eng = ColocatedEngineGroup(
            **dict(GEOM, pipeline_depth=3, sync_floor_ms=7.0)
        )
        eng.factory(None)
        assert eng.core._pipeline_depth == 3
        assert abs(eng.core._sync_floor_s - 0.007) < 1e-9

    def test_depth1_is_serial(self):
        """Depth 1 completes every generation in the dispatching call:
        the in-flight deque never survives a step."""
        group, nhs = make_cluster(KVStore, "serial", pipeline_depth=1)
        try:
            lead = wait_for_leader(nhs)
            # wait_for_leader returns the AGREED leader's replica id
            # (== its host key here): re-probing is_leader_of after
            # the wait raced suite-load leadership blips into a
            # StopIteration (tier-1 flake)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            for i in range(6):
                propose_r(nh, sess, set_cmd(f"k{i}", b"x"))
            assert not group.core._inflight
            assert group.core.stats["pipeline_overlap_s"] == 0.0
        finally:
            close_all(nhs)


class TestFusedWaves:
    """Fused commit rounds (ISSUE 15): a routable generation chains
    K=3 consensus rounds device-side and commits quiet-path proposals
    in ONE launch + ONE readback window.  Contracts:

      W1 (one readback): readback_windows counts exactly one collect
         window per completed generation (plus one per exact-gather
         fallback round) — a fused wave never pays K floors;
      W2 (fence): non-routable generations (escalation holds, stopping
         rows, deferred membership actions) dispatch single-round —
         the PR 11 fence argument keeps its <=1-launch exposure;
      W3 (exactly-once): the fused path inherits F3 — every acked
         proposal applies exactly once on every replica (the parity
         fixture of this module stays armed throughout).
    """

    def test_fused_wave_one_readback_per_wave(self):
        group, nhs = make_cluster(
            AuditKV, "fused", sync_floor_ms=5.0, fused_rounds=3,
        )
        try:
            lead = wait_for_leader(nhs)
            core = group.core
            assert core._fuse_rounds == 3
            # wait_for_leader returns the AGREED leader's replica id
            # (== its host key here): re-probing is_leader_of after
            # the wait raced suite-load leadership blips into a
            # StopIteration (tier-1 flake)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            keys = set()
            pending = []
            for i in range(24):
                k = f"fw{i}"
                keys.add(k)
                pending.append(
                    (k, nh.propose(sess, audit_set_cmd(k, i), 20.0))
                )
            for k, rs in pending:
                rs._event.wait(20.0)
                assert rs.code == 1, f"future lost for {k}: {rs.code}"
            # W1: one readback window per completed generation (+1 per
            # exact-gather fallback round), snapshotted under the core
            # lock: every launched generation is either completed or
            # still in flight, so the identity is exact even while
            # tick generations keep dispatching
            with core._lock:
                st = dict(core.stats)
                inflight = len(core._inflight)
            assert st["fused_waves"] > 0, st
            assert st["fused_rounds_stepped"] >= 3 * st["fused_waves"]
            # depth 2: host work ran while a readback was in flight
            assert st["launches"] > 5, st
            assert st["pipeline_overlap_s"] > 0, st
            assert st["readback_windows"] + inflight == (
                st["launches"] + st.get("sel_fallbacks", 0)
            ), (st, inflight)
            # W3: exactly-once applies on every replica
            journals = settle_journals(nhs, 1, keys)
            assert len(journals) == 3
            for rid, j in journals.items():
                applied = [k for _, k, _ in j if k in keys]
                assert len(applied) == len(keys), (
                    f"replica {rid}: {len(applied)} applies for "
                    f"{len(keys)} acked keys"
                )
        finally:
            close_all(nhs)
        assert not group.core._inflight and not group.core._deferred

    def test_escalation_hold_fences_to_single_round(self):
        """W2: an armed escalation hold on ANY resident row keeps new
        generations single-round (fused_fences counts them) until the
        hold drains; fusing resumes afterwards."""
        group, nhs = make_cluster(
            KVStore, "fusedesc", fused_rounds=3,
        )
        try:
            lead = wait_for_leader(nhs)
            core = group.core
            # wait_for_leader returns the AGREED leader's replica id
            # (== its host key here): re-probing is_leader_of after
            # the wait raced suite-load leadership blips into a
            # StopIteration (tier-1 flake)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            propose_r(nh, sess, set_cmd("warm", b"1"))
            with core._lock:
                alive = np.nonzero(core._lanes.alive_mask())[0]
                assert len(alive), "no resident rows"
                g = int(alive[0])
                core._lanes.esc_hold[g] = 10_000
            fences0 = core.stats["fused_fences"]
            waves0 = core.stats["fused_waves"]
            for i in range(6):
                propose_r(nh, sess, set_cmd(f"held{i}", b"1"))
            assert core.stats["fused_fences"] > fences0, core.stats
            assert core.stats["fused_waves"] == waves0, (
                "a wave dispatched under an escalation hold"
            )
            with core._lock:
                core._lanes.esc_hold[g] = 0
            for i in range(6):
                propose_r(nh, sess, set_cmd(f"free{i}", b"1"))
            assert core.stats["fused_waves"] > waves0, (
                "fusing never resumed after the hold drained"
            )
        finally:
            close_all(nhs)

    def test_fused_disabled_by_knob(self):
        """fused_rounds=1 is the PR 11 single-round loop: zero waves,
        the keyword is the reference the wave is compared with."""
        group, nhs = make_cluster(
            KVStore, "fusedoff", fused_rounds=1,
        )
        try:
            lead = wait_for_leader(nhs)
            # wait_for_leader returns the AGREED leader's replica id
            # (== its host key here): re-probing is_leader_of after
            # the wait raced suite-load leadership blips into a
            # StopIteration (tier-1 flake)
            nh = nhs[lead]
            sess = nh.get_noop_session(1)
            for i in range(6):
                propose_r(nh, sess, set_cmd(f"k{i}", b"x"))
            assert group.core.stats["fused_waves"] == 0
            assert group.core.stats["fused_fences"] == 0  # knob, not fence
        finally:
            close_all(nhs)


class TestFusedShardedRounds:
    """Forced-multi-host-device mesh run (ISSUE 15 satellite): the
    fused sharded round (``make_sharded_round(rounds=K)``) is
    bit-exact with K sequential sharded rounds AND with the
    single-device ``fused_rounds`` over the same global topology —
    proving the cross-chip ppermute lane fires BETWEEN fused rounds,
    not after the wave (a lane deferred to the wave end would diverge
    the serial legs on the first cross-device ack)."""

    @pytest.mark.slow  # tier-1 budget (ISSUE 18): 27s; the sharded
    # round's cross-device parity stays covered every run by
    # test_multichip's round/step parity variants
    def test_fused_sharded_parity_cross_device(self):
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from dragonboat_tpu.ops import route as R
        from dragonboat_tpu.ops.types import make_state

        devs = [d for d in jax.devices() if d.platform == "cpu"]
        if len(devs) < 2:
            pytest.skip("needs 2 forced host devices")
        mesh = Mesh(np.asarray(devs[:2]), ("groups",))
        P, W, E, O, BUD, BASE, K = 3, 16, 2, 16, 4, 2, 3
        M = BASE + P * BUD
        groups, REPL = 4, 3
        G = groups * REPL
        # replica-major: every group's replicas straddle device blocks
        shard_ids = np.tile(
            np.arange(1, groups + 1, dtype=np.int32), REPL
        )
        replica_ids = np.repeat(
            np.arange(1, REPL + 1, dtype=np.int32), groups
        )
        peer_ids = np.broadcast_to(
            np.arange(1, REPL + 1, dtype=np.int32), (G, P)
        ).copy()
        tabs = R.build_route_tables_mesh(
            shard_ids, replica_ids, peer_ids, 2
        )
        XB = R.xbudget_for(tabs, BUD, 2)
        dest, rank = R.build_route_tables(
            shard_ids, replica_ids, peer_ids
        )
        st = make_state(
            G, P, W, shard_ids=shard_ids, replica_ids=replica_ids,
            peer_ids=peer_ids, election_timeout=10,
            heartbeat_timeout=2,
        )
        ib = R.make_prefill(st, M, E)
        round_shard = R.make_sharded_round(
            mesh, M=M, E=E, out_capacity=O, budget=BUD, xbudget=XB,
            base=BASE, propose_leaders=True,
        )
        wave_shard = R.make_sharded_round(
            mesh, M=M, E=E, out_capacity=O, budget=BUD, xbudget=XB,
            base=BASE, propose_leaders=True, rounds=K,
        )
        fused_single = jax.jit(functools.partial(
            R.fused_rounds, rounds=K, out_capacity=O, budget=BUD,
            base=BASE, propose_leaders=True,
        ))
        args_s = [jnp.asarray(t) for t in (
            tabs.dest_local, tabs.dest_dev, tabs.rank_in_dest
        )]
        args_r = [jnp.asarray(dest), jnp.asarray(rank)]
        st_serial = st_wave = st_single = st
        ib_serial = ib_wave = ib_single = ib
        lane_tot = np.zeros((7,), np.int64)
        for _ in range(8):  # 8 waves = 24 rounds: election + commits
            for _k in range(K):
                st_serial, ib_serial, _s, _l = round_shard(
                    st_serial, ib_serial, *args_s
                )
            st_wave, ib_wave, _sw, lane = wave_shard(
                st_wave, ib_wave, *args_s
            )
            assert np.asarray(lane).shape == (2 * K, 7)
            lane_tot += np.asarray(lane, np.int64).sum(0)
            st_single, ib_single, _sf, _ef = fused_single(
                st_single, ib_single, *args_r
            )
            for f in st_serial._fields:
                a = np.asarray(getattr(st_serial, f))
                b = np.asarray(getattr(st_wave, f))
                c = np.asarray(getattr(st_single, f))
                assert np.array_equal(a, b), f"wave-vs-serial {f}"
                assert np.array_equal(a, c), f"wave-vs-single {f}"
            for f in ib_serial._fields:
                a = np.asarray(getattr(ib_serial, f))
                b = np.asarray(getattr(ib_wave, f))
                assert np.array_equal(a, b), f"inbox {f}"
        # real cross-device traffic rode the lane mid-wave, none lost
        assert lane_tot[1] > 0, "no cross-device traffic on the lane"
        assert lane_tot[3] == 0, f"xlane drops at sized budget: {lane_tot}"
        from dragonboat_tpu.ops.types import ROLE_LEADER

        assert (np.asarray(st_wave.role) == ROLE_LEADER).sum() >= groups - 1
