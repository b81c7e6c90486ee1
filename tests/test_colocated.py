"""Colocated-cluster mode: device routing in the PRODUCT path.

Three NodeHosts in one process share ONE device state via
``ColocatedEngineGroup``; co-located replicas' consensus traffic is
scattered device-side by ops/route.py instead of round-tripping the
host transport (VERDICT r2 missing #1).  These tests prove the wiring
end-to-end: elections and replication run with transport volume ~0 in
steady state, payloads reconstruct across replicas through the shared
entry cache, and the cold paths (reads, membership, restart) still
work through the same materialize/re-upload dance as the base engine.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from dragonboat_tpu import (
    EngineConfig,
    ExpertConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import (
    ADDRS,
    KVStore,
    propose_r,
    set_cmd,
    shard_config,
    wait_for_leader,
)
from test_vector_engine import read_r

# budget 4 covers a leader's worst per-peer launch (several deferred
# ticks' heartbeats + append replicate + commit broadcast) so steady
# state stays fully on-device
GEOM = dict(capacity=16, P=5, W=32, M=8, E=4, O=32, budget=4)


@pytest.fixture(scope="module", autouse=True)
def warm_colocated():
    """Compile the colocated programs (kernel at the wider inbox + the
    route program) once up front; the persistent cache makes reruns
    cheap."""
    group = ColocatedEngineGroup(**GEOM)
    group.factory(None)  # builds the core -> runs _warm()


def colo_shard_config(replica_id, shard_id=1, **kw):
    kw.setdefault("election_rtt", 20)
    kw.setdefault("heartbeat_rtt", 2)
    # PreVote + CheckQuorum(lease): on a loaded CPU backend, launch
    # latency jitter can push a follower past its election timeout a
    # beat before the routed heartbeat slot is processed; the lease
    # rejects those disruptive candidacies (dragonboat's recommended
    # production posture, reference: config.Config PreVote/CheckQuorum)
    kw.setdefault("pre_vote", True)
    kw.setdefault("check_quorum", True)
    return shard_config(replica_id, shard_id=shard_id, **kw)


def make_colocated_cluster(rtt_ms=5):
    reset_inproc_network()
    group = ColocatedEngineGroup(**GEOM)
    nhs = {}
    for rid in ADDRS:
        shutil.rmtree(f"/tmp/nh-colo-{rid}", ignore_errors=True)
        nhs[rid] = NodeHost(
            NodeHostConfig(
                nodehost_dir=f"/tmp/nh-colo-{rid}",
                rtt_millisecond=rtt_ms,
                raft_address=ADDRS[rid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=group.factory,
                ),
            )
        )
    return group, nhs


@pytest.fixture
def ccluster():
    group, nhs = make_colocated_cluster()
    for rid, nh in nhs.items():
        nh.start_replica(ADDRS, False, KVStore, colo_shard_config(rid))
    yield group, nhs
    for nh in nhs.values():
        nh.close()


def transport_sent(nhs):
    return {r: nh.transport.metrics["sent"] for r, nh in nhs.items()}


class TestColocatedCluster:
    def test_one_shared_core(self, ccluster):
        group, nhs = ccluster
        cores = {id(nh.engine.step_engine.core) for nh in nhs.values()}
        assert len(cores) == 1
        assert nhs[1].engine.step_engine.core is group.core

    def test_consensus_routes_on_device(self, ccluster):
        group, nhs = ccluster
        wait_for_leader(nhs)
        nh = nhs[1]
        s = nh.get_noop_session(1)
        for i in range(20):
            propose_r(nh, s, set_cmd(f"k{i}", str(i).encode()))
        # every replica applied the replicated payloads (reconstructed
        # from the shared entry cache, not the wire)
        for rid in ADDRS:
            assert read_r(nhs[rid], 1, "k19") == b"19"
        st = group.core.stats
        assert st["routed_delivered"] > 0, st
        assert st["launches"] > 0, st

    def test_steady_state_transport_is_quiet(self, ccluster):
        """Once all rows are device-resident, heartbeats and replication
        ride the device route: the host transport goes (almost) silent
        while routed traffic keeps flowing — the VERDICT done-criterion
        'transport message count ~0 for co-located peers'."""
        group, nhs = ccluster
        wait_for_leader(nhs)
        s = nhs[1].get_noop_session(1)
        propose_r(nhs[1], s, set_cmd("warm", b"1"))
        # settle: let every replica go device-resident
        time.sleep(1.0)
        for _ in range(20):
            sent0 = transport_sent(nhs)
            routed0 = group.core.stats["routed_delivered"]
            time.sleep(1.0)
            sent1 = transport_sent(nhs)
            routed1 = group.core.stats["routed_delivered"]
            wire = sum(sent1.values()) - sum(sent0.values())
            routed = routed1 - routed0
            # a fully-resident window: consensus alive on the device,
            # nothing on the wire
            if routed > 0 and wire == 0:
                return
        raise AssertionError(
            f"no quiet-wire window: wire delta {wire}, routed {routed}"
        )

    def test_payloads_survive_follower_apply(self, ccluster):
        """Routed REPLICATE carries no cmd bytes; followers must apply
        the true payload (cache reconstruction), not empty noops."""
        group, nhs = ccluster
        wait_for_leader(nhs)
        s = nhs[1].get_noop_session(1)
        blob = bytes(range(256)) * 4
        propose_r(nhs[1], s, set_cmd("blob", blob))
        deadline = time.time() + 10.0
        while time.time() < deadline:
            try:
                if all(
                    nhs[r].stale_read(1, "blob") == blob for r in ADDRS
                ):
                    return
            except Exception:
                pass
            time.sleep(0.05)
        raise AssertionError("followers never applied the routed payload")

    def test_reads_and_membership_cold_paths(self, ccluster):
        group, nhs = ccluster
        wait_for_leader(nhs)
        nh = nhs[1]
        s = nh.get_noop_session(1)
        propose_r(nh, s, set_cmd("pre", b"1"))
        for rid in ADDRS:
            assert read_r(nhs[rid], 1, "pre") == b"1"
        from test_nodehost import add_non_voting_poll

        # goal-state polling, not per-attempt acks (r03 verdict #5)
        m2 = add_non_voting_poll(nh, 1, 9, "nh-9")
        assert 9 in m2.non_votings
        propose_r(nh, s, set_cmd("post", b"2"))
        assert read_r(nh, 1, "post") == b"2"

    def test_replica_restart_rejoins_device(self, ccluster):
        group, nhs = ccluster
        wait_for_leader(nhs)
        s = nhs[1].get_noop_session(1)
        for i in range(5):
            propose_r(nhs[1], s, set_cmd(f"r{i}", str(i).encode()))
        nhs[3].stop_replica(1, 3)
        propose_r(nhs[1], s, set_cmd("while-down", b"x"), deadline=15.0)
        nhs[3].start_replica(ADDRS, False, KVStore, colo_shard_config(3))
        deadline = time.time() + 15.0
        while time.time() < deadline:
            try:
                if nhs[3].stale_read(1, "while-down") == b"x":
                    break
            except Exception:
                pass
            time.sleep(0.05)
        else:
            raise AssertionError("restarted replica never caught up")
        # the rejoined replica holds a fresh row and keeps committing
        propose_r(nhs[1], s, set_cmd("after", b"y"))
        assert read_r(nhs[3], 1, "after") == b"y"

    def test_multi_shard_routing(self, ccluster):
        group, nhs = ccluster
        for shard in (2, 3):
            for rid, nh in nhs.items():
                nh.start_replica(
                    ADDRS, False, KVStore,
                    colo_shard_config(rid, shard_id=shard),
                )
        for shard in (1, 2, 3):
            wait_for_leader(nhs, shard_id=shard, timeout=20.0)
            s = nhs[1].get_noop_session(shard)
            propose_r(
                nhs[1], s, set_cmd(f"s{shard}", bytes([shard])),
                deadline=20.0,
            )
        for shard in (1, 2, 3):
            assert read_r(nhs[2], shard, f"s{shard}") == bytes([shard])


class TestColocatedRebasing:
    """Per-shard group rebasing: the colocated 64-bit story (r03
    verdict #4 — the flagship path used to pin base 0 and age shards
    off the device at 2^31)."""

    def test_multi_rebase_under_traffic(self):
        """A tiny rebase_chunk forces several whole-shard rebases while
        routed consensus traffic flows; every write must stay readable
        on every member and the device path must stay in use."""
        reset_inproc_network()
        geom = dict(GEOM)
        geom["rebase_chunk"] = 64
        group = ColocatedEngineGroup(**geom)
        nhs = {}
        for rid in ADDRS:
            shutil.rmtree(f"/tmp/nh-colo-{rid}", ignore_errors=True)
            nhs[rid] = NodeHost(
                NodeHostConfig(
                    nodehost_dir=f"/tmp/nh-colo-{rid}",
                    rtt_millisecond=5,
                    raft_address=ADDRS[rid],
                    expert=ExpertConfig(
                        engine=EngineConfig(exec_shards=1, apply_shards=2),
                        step_engine_factory=group.factory,
                    ),
                )
            )
        try:
            for rid, nh in nhs.items():
                nh.start_replica(ADDRS, False, KVStore, colo_shard_config(rid))
            wait_for_leader(nhs)
            s = nhs[1].get_noop_session(1)
            for i in range(200):
                propose_r(nhs[1], s, set_cmd(f"rb{i}", str(i).encode()))
            core = group.core
            with core._lock:
                rebases = core.stats["shard_rebases"]
                base = core._shard_base.get(1, 0)
            assert rebases >= 2, core.stats
            assert base > 0 and base % geom["W"] == 0
            assert core.stats["routed_delivered"] > 0
            assert core.stats["divergence_halts"] == 0
            for rid in ADDRS:
                assert read_r(nhs[rid], 1, "rb199") == b"199"
        finally:
            for nh in nhs.values():
                nh.close()

    def test_commits_across_2_31_on_device(self, tmp_path):
        """Disaster-recovery import seeds a shard whose log begins past
        2^31 (reference: uint64 indexes in raftpb [U]); the colocated
        cluster must elect, establish a shared shard base, and commit
        client writes ON THE DEVICE PATH at absolute indexes > 2^31."""
        from dragonboat_tpu import tools
        from dragonboat_tpu.transport.wire import encode_snapshot_meta

        B31 = 2**31
        # phase 1: author an export whose container sits past 2^31 —
        # the same v2 container + META pair export_snapshot produces,
        # built directly so the "cluster ran for 2^31 entries" history
        # doesn't have to be simulated
        import io
        import os
        import pickle

        from dragonboat_tpu.pb import Membership, Snapshot
        from dragonboat_tpu.rsm.session import SessionManager
        from dragonboat_tpu.storage.snapshotio import SnapshotWriter

        export_dir = str(tmp_path / "export")
        os.makedirs(export_dir)
        membership = Membership(config_change_id=1, addresses=dict(ADDRS))
        buf = io.BytesIO()
        w = SnapshotWriter(
            buf, index=B31 + 100, term=3, membership=membership,
            sessions=SessionManager().serialize(), on_disk=False,
        )
        w.write(pickle.dumps({"seed": b"s"}))  # KVStore.save_snapshot shape
        w.close()
        payload = buf.getvalue()
        with open(f"{export_dir}/snapshot.bin", "wb") as f:
            f.write(payload)
        meta = Snapshot(index=B31 + 100, term=3, membership=membership,
                        shard_id=1, file_size=len(payload))
        with open(f"{export_dir}/META", "wb") as f:
            f.write(encode_snapshot_meta(meta))

        # phase 2: import into a fresh colocated cluster
        reset_inproc_network()
        group = ColocatedEngineGroup(**GEOM)
        nhs = {}
        for rid in ADDRS:
            shutil.rmtree(f"/tmp/nh-colo-{rid}", ignore_errors=True)
            nhs[rid] = NodeHost(
                NodeHostConfig(
                    nodehost_dir=f"/tmp/nh-colo-{rid}",
                    rtt_millisecond=5,
                    raft_address=ADDRS[rid],
                    expert=ExpertConfig(
                        engine=EngineConfig(exec_shards=1, apply_shards=2),
                        step_engine_factory=group.factory,
                    ),
                )
            )
        try:
            for rid, nh in nhs.items():
                tools.import_snapshot(nh, export_dir, 1, rid, dict(ADDRS))
                nh.start_replica(ADDRS, False, KVStore, colo_shard_config(rid))
            wait_for_leader(nhs)
            s = nhs[1].get_noop_session(1)
            for i in range(40):
                propose_r(nhs[1], s, set_cmd(f"hi{i}", str(i).encode()))
            core = group.core
            with core._lock:
                base = core._shard_base.get(1, 0)
                stepped = core.stats["device_rows_stepped"]
            committed = nhs[1]._nodes[1].peer.raft.log.committed
            assert committed > B31 + 100, committed
            assert base > B31, f"shard base never established: {base}"
            assert base % GEOM["W"] == 0
            assert stepped > 0, core.stats
            assert core.stats["divergence_halts"] == 0
            for rid in ADDRS:
                assert read_r(nhs[rid], 1, "hi39") == b"39"
                assert read_r(nhs[rid], 1, "seed") == b"s"
        finally:
            for nh in nhs.values():
                nh.close()


class TestEntryCachePublishing:
    """Unit tests on the shared entry cache's publish rules."""

    def test_witness_row_never_publishes_stripped_entries(self):
        """A witness's own log holds stripped metadata entries under the
        SAME (index, term) keys as the real ones; letting its upload
        publish them would overwrite real payloads in the shared cache
        and silently diverge any replica that reconstructs from it
        (review finding, r4).  reference: witness metadata replication,
        raft.go makeMetadataEntry [U]."""
        from dragonboat_tpu.pb import Entry, EntryType
        from dragonboat_tpu.raft.raft import Raft

        core = ColocatedEngineGroup(**GEOM)
        core.factory(None)
        eng = core.core

        real = [
            Entry(term=1, index=i, type=EntryType.APPLICATION,
                  cmd=f"cmd{i}".encode())
            for i in range(1, 6)
        ]
        voter = Raft(1, 1, {1: "a", 2: "b"}, witnesses={3: "c"})
        voter.log.inmem.merge(real)
        eng._publish_ring_window(voter)
        assert eng._cache_lookup(voter, 3, 1).cmd == b"cmd3"

        # the witness replica's log: stripped forms of the same entries
        witness = Raft(1, 3, {1: "a", 2: "b"}, witnesses={3: "c"},
                       is_witness=True)
        witness.log.inmem.merge(
            [Raft._to_witness_entry(e) for e in real]
        )
        eng._publish_ring_window(witness)
        # real payloads survive: the witness published nothing
        assert eng._cache_lookup(voter, 3, 1).cmd == b"cmd3"
        # witness RECEIVERS still get the stripped form at lookup
        got = eng._cache_lookup(witness, 3, 1)
        assert got.cmd == b"" and got.type == EntryType.METADATA

    def test_cache_depth_covers_launch_append_volume(self):
        """Depth must cover the stamp-to-consumption gap of a routed
        append under a proposal storm (~M*E entries/launch), not just
        the ring window (chaos finding: rare fail-stops at W=8)."""
        geom = dict(GEOM)
        geom.update(W=4, M=8, E=4)
        core = ColocatedEngineGroup(**geom)
        core.factory(None)
        assert core.core._cache_depth >= 8 * 8 * 4


class TestColocatedQuiesce:
    """Quiesce through the COLOCATED fast tick lane: device-resident
    rows whose only input is the tick lane take the fast-lane quiesce
    path (plan_ok short-circuit), must still idle out, park, and wake
    on activity (reference: quiesceManager + workReady [U])."""

    @pytest.mark.flaky_isolated
    def test_quiesce_enters_and_wakes_through_fast_lane(self):
        # flaky_isolated: park requires EVERY member idle for a full
        # quiesce threshold; residual CPU load from earlier modules can
        # stretch the 2ms-rtt tick cadence past the poll deadline
        # (passes in isolation — ROADMAP rotating flake; the conftest
        # hook retries once after the process settles)
        group, nhs = make_colocated_cluster(rtt_ms=2)
        try:
            for rid, nh in nhs.items():
                nh.start_replica(
                    ADDRS, False, KVStore,
                    colo_shard_config(rid, quiesce=True, election_rtt=10),
                )
            wait_for_leader(nhs)
            s = nhs[1].get_noop_session(1)
            propose_r(nhs[1], s, set_cmd("a", b"1"))

            # idle out: threshold = election_rtt*10 = 100 ticks = 200ms
            # at rtt 2ms; poll until every member parks the shard
            deadline = time.time() + 30.0
            while time.time() < deadline:
                if all(1 in nh._parked for nh in nhs.values()):
                    break
                time.sleep(0.05)
            assert all(1 in nh._parked for nh in nhs.values()), [
                dict(nh._parked) for nh in nhs.values()
            ]
            # fast lane must actually have engaged while idling out
            assert group.core.stats.get("fast_lane_rows", 0) > 0

            time.sleep(0.5)
            propose_r(nhs[1], s, set_cmd("b", b"2"))
            for rid in ADDRS:
                assert read_r(nhs[rid], 1, "b") == b"2"
        finally:
            for nh in nhs.values():
                nh.close()


class TestLaunchFailureIsCounted:
    """A launch that dies must not be silent: the cluster survives it
    (rows roll back and re-upload) and the failure shows in a counter
    (``pipeline_resets`` on the core, ``step_worker_failures`` on the
    exec engine) — what chip_smoke.py requires to be zero."""

    def test_failed_launch_bumps_pipeline_resets(self, ccluster, monkeypatch):
        from dragonboat_tpu.ops import colocated

        group, nhs = ccluster
        wait_for_leader(nhs)
        s = nhs[1].get_noop_session(1)
        propose_r(nhs[1], s, set_cmd("a", b"1"))
        assert group.core.stats["pipeline_resets"] == 0

        real = colocated._select_and_blob
        fired = []

        def select_raises_once(*a, **kw):
            if not fired:
                fired.append(1)
                raise RuntimeError("injected launch failure")
            return real(*a, **kw)

        monkeypatch.setattr(colocated, "_select_and_blob",
                            select_raises_once)
        deadline = time.time() + 10.0
        while not fired and time.time() < deadline:
            time.sleep(0.01)
        propose_r(nhs[1], s, set_cmd("b", b"2"), deadline=30.0)
        assert group.core.stats["pipeline_resets"] == 1
        assert sum(nh.engine.step_worker_failures
                   for nh in nhs.values()) == 1
        for rid in ADDRS:
            assert read_r(nhs[rid], 1, "b") == b"2"

    def test_raising_step_engine_bumps_step_worker_failures(
        self, ccluster, monkeypatch
    ):
        group, nhs = ccluster
        wait_for_leader(nhs)
        eng = nhs[2].engine
        real = eng.step_engine.step_shards
        fired = []

        def step_raises_once(nodes, worker_id):
            if not fired:
                fired.append(1)
                raise RuntimeError("injected step failure")
            return real(nodes, worker_id)

        monkeypatch.setattr(eng.step_engine, "step_shards",
                            step_raises_once)
        deadline = time.time() + 10.0
        while not fired and time.time() < deadline:
            time.sleep(0.01)
        assert eng.step_worker_failures == 1
        s = nhs[1].get_noop_session(1)
        propose_r(nhs[1], s, set_cmd("c", b"3"), deadline=30.0)
        for rid in ADDRS:
            assert read_r(nhs[rid], 1, "c") == b"3"


class TestChipSmoke:
    """chip_smoke.py is the script the driver runs on the accelerator;
    here only its control flow can be checked, at 8 groups on the CPU."""

    SMOKE = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chip_smoke.py",
    )

    def _run(self, *flags):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, self.SMOKE, *flags], env=env,
            capture_output=True, text=True, timeout=120,
        )

    def test_dry_run_passes_and_is_labelled(self):
        p = self._run("--allow-cpu", "--shards", "8")
        assert p.returncode == 0, p.stderr[-2000:]
        # two JSON lines: the report, then the driver's verdict last
        rep, verdict = map(json.loads, p.stdout.strip().splitlines()[-2:])
        assert set(verdict) == {"ok", "device"}
        assert verdict["ok"] is True
        assert set(verdict["device"]) == {"platform", "kind", "count"}
        assert verdict["device"]["platform"] == "cpu"
        assert isinstance(verdict["device"]["count"], int)
        assert rep["ok"] is True and rep["dryrun"] is True
        assert rep["device"] == verdict["device"]
        assert set(rep) >= {
            "ok", "device", "dryrun", "chips", "versions",
            "compile_cache", "wal_writer", "wal_fs", "shards",
            "replicas", "capacity", "seed", "setup_s",
            "leader_coverage", "writes", "reads", "jitcheck_retraces",
            "step_worker_failures", "host_rows_stepped_in_write_window",
            "leaked_threads", "engine", "engine_write_window",
            "blob_wait_ms_per_launch", "readback_probe", "checks",
            "total_s",
        }
        assert set(rep["setup_s"]) == {"warmup_s", "boot_s", "election_s"}
        assert set(rep["versions"]) == {"python", "jax", "jaxlib", "libtpu"}
        assert rep["wal_writer"] == "native"
        assert rep["leader_coverage"] == "8/8"
        assert rep["writes"]["acked"] == 8 and rep["writes"]["failed"] == 0
        assert rep["reads"] == {"linearizable_ok": "8/8",
                                "replica_ok": "24/24"}
        assert rep["jitcheck_retraces"] == []
        assert all(rep["checks"].values()), rep["checks"]
        for k in ("launches", "pipeline_resets", "t_plan_ms",
                  "t_upload_ms", "t_dispatch_ms", "t_dev_blob_ms",
                  "t_detail_ms", "t_updates_ms", "t_persist_ms"):
            assert k in rep["engine"], k

    def test_refuses_to_run_without_an_accelerator(self):
        p = self._run()
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "no accelerator" in p.stderr

    def test_compile_cache_rule(self, monkeypatch, tmp_path):
        from dragonboat_tpu.ops import placement

        class FakeJax:
            class config:
                calls = []

                @classmethod
                def update(cls, key, value):
                    cls.calls.append((key, value))

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert placement.configure_compile_cache(FakeJax) == str(tmp_path)
        # JAX reads the variable itself: no directory is set in code
        assert [k for k, _ in FakeJax.config.calls] == [
            "jax_persistent_cache_min_compile_time_secs"
        ]

        FakeJax.config.calls.clear()
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(os.path.dirname(self.SMOKE), ".jax_cache")
        assert placement.configure_compile_cache(FakeJax) == want
        assert ("jax_compilation_cache_dir", want) in FakeJax.config.calls


def test_coalesce_scan_skips_one_launch_inside_its_floor_and_no_second():
    """A launch that carries its caller's nodes alone feeds ticks to
    that member's rows alone; inside the scan's 200 ms floor at most
    ONE launch goes without a scan (two in a row starved the leases of
    the other members' leaders: PERF.md section 6, PR 34), and the
    cost rule (a scan at most every ten times its own length) still
    overrides that."""
    from types import SimpleNamespace

    from dragonboat_tpu.ops.colocated import ColocatedVectorEngine

    busy = SimpleNamespace(stopped=False, stopping=False,
                           has_work=lambda: True)
    core = SimpleNamespace(
        _last_coalesce_scan=0.0, _scan_cost=0.0, _scan_skipped=False,
        _meta={1: SimpleNamespace(node=busy)}, stats={"coalesced_rows": 0})

    def launch():
        time.sleep(0.002)  # well over ten times what this scan costs
        return len(ColocatedVectorEngine._coalesce(core, []))

    # 2 ms apart, well inside the floor: scan, skip, scan, skip
    assert [launch() for _ in range(6)] == [1, 0, 1, 0, 1, 0]
    assert core.stats["coalesced_rows"] == 3
    # past the floor a scan follows a scan
    core._last_coalesce_scan = time.monotonic() - 0.3
    core._scan_skipped = False
    assert launch() == 1
    # a scan that costs 50 ms is made at most every 500 ms, whatever
    # was skipped (the mass-start case the throttle was written for)
    core._scan_cost = 0.05
    core._last_coalesce_scan = time.monotonic() - 0.3
    assert [launch() for _ in range(4)] == [0, 0, 0, 0]
    core._last_coalesce_scan = time.monotonic() - 0.6
    assert launch() == 1
