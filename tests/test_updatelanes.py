"""Array-side ``pb.Update`` lanes (ISSUE 13 / ROADMAP item 1).

The merge tails now classify a generation's effects ARRAY-SIDE: one
``hostplane.plan_update_sync`` pass over the ``UpdateLanes`` SoA block
diffs the merged values against the last host sync and yields per-row
``U_*`` effect bits; rows with no heavy sections skip the per-row
``get_update`` object walk and batch into one ``save_state_lanes``
persist per LogDB (docs/PARITY.md "Update-lane contract").  These
tests hold the lane plane to the scalar twin:

* fabricated generation traces — seeded mixed election / commit /
  membership scripts driven through the SAME lane state both paths
  read, crafted effect-bit rows, the all-false-mask no-op invariant
  and the absolute-frame (rebase-invariance) contract;
* a LIVE ColocatedCluster run with the in-engine parity checker
  (``DRAGONBOAT_TPU_HOSTPLANE_PARITY``'s test-side twin) armed the
  whole time, proving the lane path actually carries product traffic
  (``lane_rows`` > 0) with zero divergence halts;
* a sharded-mesh run at 2-8 forced host devices (conftest forces 8
  CPU devices) proving the lane block composes as contiguous
  per-device slices under the ``ops/placement.py`` row-block contract.

jaxcheck note: the lanes are numpy-only (no jitted entry points), so
the device-plane audit surface is unchanged — covered by
tests/test_jaxcheck.py's zero-unbaselined tree test.
"""
import shutil
import time

import numpy as np
import pytest

from dragonboat_tpu.ops import hostplane as hp
from dragonboat_tpu.ops import placement
from dragonboat_tpu.ops.types import (
    N_VALS,
    R_COMMIT,
    R_LAST,
    R_LEADER,
    R_ROLE,
    R_TERM,
    R_VOTE,
    ROLE_CANDIDATE,
    ROLE_FOLLOWER,
    ROLE_LEADER,
    U_COMMIT,
    U_LEADER,
    U_LOST_LEAD,
    U_ROLE,
    U_STATE,
    UL_N,
)


def _plan_and_check(old_w, sum_k, vals, bases):
    plan = hp.plan_update_sync(old_w, sum_k, vals, bases)
    hp.assert_update_plan_parity(old_w, sum_k, vals, bases, plan)
    return plan


def _rand_gen(rng, n, lanes_w, mode):
    """One fabricated generation against the CURRENT lane words for
    ``n`` rows: a subset carries values (sum_k >= 0) shaped by
    ``mode`` — election (term/vote/leader churn), commit (advance with
    entries in range), membership (role/leader flips: the add/evict
    transition shape), steady (values == lane words: no-op rows)."""
    aff = rng.random(n) < {"election": 0.4, "commit": 0.25,
                           "membership": 0.15, "steady": 0.5}[mode]
    sr = np.nonzero(aff)[0]
    m = len(sr)
    sum_k = np.full((n,), -1, np.int64)
    sum_k[sr] = np.arange(m)
    vals = np.zeros((m, N_VALS), np.int64)
    # start from the current words so unchanged columns are realistic
    vals[:, :UL_N] = lanes_w[:, sr].T
    if mode == "election":
        vals[:, R_TERM] += rng.integers(0, 3, m)
        vals[:, R_VOTE] = rng.integers(0, 4, m)
        vals[:, R_LEADER] = rng.integers(0, 4, m)
        vals[:, R_ROLE] = rng.choice(
            [ROLE_FOLLOWER, ROLE_CANDIDATE, ROLE_LEADER], m
        )
    elif mode == "commit":
        vals[:, R_COMMIT] += rng.integers(0, 3, m)
        vals[:, R_LAST] = np.maximum(
            vals[:, R_LAST], vals[:, R_COMMIT]
        )
    elif mode == "membership":
        vals[:, R_ROLE] = rng.choice([ROLE_FOLLOWER, ROLE_LEADER], m)
        vals[:, R_LEADER] = rng.integers(0, 4, m)
    return sum_k, vals, sr


class TestFabricatedTraces:
    def test_mixed_script_parity(self):
        """Seeded mixed election/commit/membership script: every
        generation plans against the lane state the PREVIOUS
        generations produced (the real lifecycle), and every plan must
        match the scalar twin bit for bit."""
        rng = np.random.default_rng(1313)
        for n in (8, 64, 257):
            lanes = hp.UpdateLanes(n)
            for g in range(n):
                lanes.seed_row(g, 1, 0, 0, 0, ROLE_FOLLOWER, 0)
            bases = rng.integers(0, 1 << 20, n).astype(np.int64)
            script = ["election", "commit", "membership", "commit",
                      "steady", "election", "commit", "steady"]
            for mode in script:
                sum_k, vals, sr = _rand_gen(rng, n, lanes.words, mode)
                # vals carry the DEVICE frame for commit/last
                vals[:, R_COMMIT] -= bases[sr]
                vals[:, R_LAST] -= bases[sr]
                plan = _plan_and_check(
                    lanes.words[:, :], sum_k, vals, bases
                )
                lanes.words[:, :] = plan.words
                # absolute-frame invariant: the write-back restored
                # the bases the device frame subtracted
                assert (
                    plan.words[R_COMMIT, sr]
                    == vals[:, R_COMMIT] + bases[sr]
                ).all()

    def test_all_false_mask_is_noop(self):
        """sum_k all -1 (no row carried values): words pass through
        unchanged and every effect bit is 0 — the no-op invariant the
        tick-only generation rides."""
        rng = np.random.default_rng(7)
        old_w = rng.integers(0, 100, (UL_N, 33)).astype(np.int64)
        sum_k = np.full((33,), -1, np.int64)
        vals = np.zeros((0, N_VALS), np.int64)
        plan = _plan_and_check(old_w, sum_k, vals, np.zeros(33, np.int64))
        assert np.array_equal(plan.words, old_w)
        assert not plan.ubits.any()

    def test_identical_values_yield_zero_ubits(self):
        """A row whose merged values equal its last sync owes NOTHING:
        no persist, no role resync, no notification."""
        old_w = np.asarray(
            [[5], [2], [30], [1], [ROLE_FOLLOWER], [40]], np.int64
        )
        vals = np.zeros((1, N_VALS), np.int64)
        vals[0, :UL_N] = [5, 2, 30, 1, ROLE_FOLLOWER, 40]
        plan = _plan_and_check(
            old_w, np.zeros(1, np.int64), vals, np.zeros(1, np.int64)
        )
        assert plan.ubits[0] == 0

    def test_effect_bits_crafted_rows(self):
        """One row per effect class, the update-lane contract's case
        table (docs/PARITY.md)."""
        base = [5, 2, 30, 1, ROLE_FOLLOWER, 40]
        rows = [
            # (new vals delta, expected ubits)
            ({R_TERM: 6}, U_STATE),                        # term moved
            ({R_VOTE: 3}, U_STATE),                        # vote moved
            ({R_COMMIT: 31}, U_STATE | U_COMMIT),          # commit fwd
            ({R_LEADER: 2}, U_LEADER),                     # leader word
            ({R_ROLE: ROLE_CANDIDATE}, U_ROLE),            # role word
            ({}, 0),                                       # byte-equal
        ]
        n = len(rows)
        old_w = np.tile(np.asarray(base, np.int64)[:, None], (1, n))
        vals = np.zeros((n, N_VALS), np.int64)
        for i, (delta, _) in enumerate(rows):
            v = list(base)
            for c, x in delta.items():
                v[c] = x
            vals[i, :UL_N] = v
        plan = _plan_and_check(
            old_w, np.arange(n, dtype=np.int64), vals,
            np.zeros(n, np.int64),
        )
        for i, (_, want) in enumerate(rows):
            assert plan.ubits[i] == want, (i, plan.ubits[i], want)

    def test_lost_leadership_bit(self):
        """LEADER -> anything else sets U_LOST_LEAD (pending device
        reads must drop: confirmations will never arrive)."""
        old_w = np.asarray(
            [[5], [2], [30], [1], [ROLE_LEADER], [40]], np.int64
        )
        vals = np.zeros((1, N_VALS), np.int64)
        vals[0, :UL_N] = [6, 2, 30, 2, ROLE_FOLLOWER, 40]
        plan = _plan_and_check(
            old_w, np.zeros(1, np.int64), vals, np.zeros(1, np.int64)
        )
        ub = int(plan.ubits[0])
        assert ub & U_LOST_LEAD
        assert ub & U_ROLE and ub & U_STATE and ub & U_LEADER
        # the reverse transition (gain) must NOT set it
        old_w[R_ROLE, 0] = ROLE_FOLLOWER
        vals[0, R_ROLE] = ROLE_LEADER
        plan = _plan_and_check(
            old_w, np.zeros(1, np.int64), vals, np.zeros(1, np.int64)
        )
        assert not int(plan.ubits[0]) & U_LOST_LEAD

    def test_base_conversion_is_absolute(self):
        """commit/last convert device frame -> absolute frame through
        ``bases``; term/vote/leader/role do not.  A rebase (same
        absolute commit, shifted base + device word) therefore yields
        ZERO effect bits — rebases never perturb the lanes."""
        old_w = np.asarray(
            [[5], [2], [1030], [1], [ROLE_FOLLOWER], [1040]], np.int64
        )
        vals = np.zeros((1, N_VALS), np.int64)
        vals[0, :UL_N] = [5, 2, 30, 1, ROLE_FOLLOWER, 40]
        plan = _plan_and_check(
            old_w, np.zeros(1, np.int64), vals,
            np.asarray([1000], np.int64),
        )
        assert plan.ubits[0] == 0
        assert plan.words[R_COMMIT, 0] == 1030
        assert plan.words[R_LAST, 0] == 1040

    def test_parity_error_names_the_lane(self):
        bad = hp.UpdateSyncPlan(
            words=np.zeros((UL_N, 1), np.int64),
            ubits=np.asarray([U_STATE], np.int64),
        )
        with pytest.raises(hp.HostPlaneParityError, match="update_"):
            hp.assert_update_plan_parity(
                np.zeros((UL_N, 1), np.int64), np.full(1, -1, np.int64),
                np.zeros((0, N_VALS), np.int64), np.zeros(1, np.int64),
                bad,
            )


class TestUpdateLanesBlock:
    def test_seed_row_roundtrip(self):
        lanes = hp.UpdateLanes(4)
        lanes.seed_row(2, 7, 3, 55, 1, ROLE_LEADER, 60)
        assert lanes.words[:, 2].tolist() == [7, 3, 55, 1, ROLE_LEADER, 60]
        assert not lanes.words[:, [0, 1, 3]].any()

    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    def test_device_slices_tile_the_block(self, n_dev):
        """The chip-sharded layout contract: device d's slice is a
        zero-copy VIEW of columns [d*Gl, (d+1)*Gl), the slices tile
        the block exactly, and each engine row's slice matches
        placement.device_of_row."""
        cap = 16
        lanes = hp.UpdateLanes(cap)
        rng = np.random.default_rng(5)
        lanes.words[:] = rng.integers(0, 99, lanes.words.shape)
        per = placement.rows_per_device(cap, n_dev)
        seen = []
        for d in range(n_dev):
            sl = lanes.device_slice(d, n_dev)
            assert sl.shape == (UL_N, per)
            assert np.shares_memory(sl, lanes.words)  # view, not copy
            assert np.array_equal(
                sl, lanes.words[:, d * per:(d + 1) * per]
            )
            seen.append(sl)
        assert np.array_equal(np.concatenate(seen, axis=1), lanes.words)
        # row->device agreement with the placement contract
        for g in range(cap):
            d = placement.device_of_row(g, cap, n_dev)
            sl = lanes.device_slice(d, n_dev)
            sl[0, g - d * per] = 12345  # write through the view...
            assert lanes.words[0, g] == 12345  # ...lands in the block


class TestLiveClusterParity:
    """LIVE colocated traffic with the in-engine parity checker armed:
    elections, proposals and a membership change flow through the lane
    path (lane_rows > 0) with zero parity failures and zero
    divergence halts."""

    def test_live_cluster_lane_path(self):
        import test_chaos_colocated as tcc
        from test_nodehost import set_cmd, wait_for_leader

        old_parity = hp.PARITY
        hp.PARITY = True
        hp.PARITY_FAILURES.clear()
        cluster = tcc.ColocatedCluster(seed=131)

        def propose(i):
            for nh in cluster.nhs.values():
                try:
                    s = nh.get_noop_session(1)
                    nh.sync_propose(
                        s, set_cmd(f"k{i}", f"v{i}".encode()), timeout=5.0
                    )
                    return
                except Exception:  # noqa: BLE001 — try the next host
                    continue

        try:
            wait_for_leader(cluster.nhs)
            for i in range(30):
                propose(i)
            # membership change: evictions + re-uploads re-seed lanes
            lead_nh = next(
                (nh for nh in cluster.nhs.values() if nh.is_leader_of(1)),
                None,
            )
            if lead_nh is not None:
                try:
                    lead_nh.sync_request_add_replica(
                        1, 9, "colo-chaos-1", timeout=10.0
                    )
                except Exception:  # noqa: BLE001 — churny add may
                    pass           # time out; lanes exercised anyway
            for i in range(30, 40):
                propose(i)
            time.sleep(0.3)
            core = cluster.group.core
            st = core.stats
            assert st.get("launches", 0) > 0
            # the lane path CARRIED rows (batched persists happened)
            assert st.get("lane_rows", 0) > 0, st
            assert st.get("divergence_halts", 0) == 0
            assert hp.PARITY_FAILURES == [], hp.PARITY_FAILURES[:3]
            # lanes mirror the scalar rafts for every resident row
            with core._lock:
                for (sid, rid), g in core._row_of.items():
                    meta = core._meta.get(g)
                    if meta is None:
                        continue
                    r = meta.node.peer.raft
                    w = core._ulanes.words[:, g]
                    assert w[R_TERM] == r.term, (sid, rid)
                    assert w[R_COMMIT] <= r.log.committed, (sid, rid)
        finally:
            hp.PARITY = old_parity
            cluster.close()


@pytest.mark.parametrize(
    "n_dev",
    # the 8-device variant is slow-tier only (tier-1 budget, ISSUE 18:
    # 24s); the 2-device run keeps the sliced-lane signal every run
    [2, pytest.param(8, marks=pytest.mark.slow)],
)
def test_sharded_mesh_lane_slices(n_dev):
    """ColocatedEngineGroup(mesh=...) at forced host devices: live
    traffic runs with parity armed, and the lane block composes as
    contiguous per-device slices — every resident row's lane column
    lives in the slice of the device placement assigns it to (the
    chip-sharded-by-construction acceptance gate)."""
    import jax

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
    from dragonboat_tpu.transport.inproc import reset_inproc_network
    from jax.sharding import Mesh

    from test_nodehost import KVStore, set_cmd

    devs = [d for d in jax.devices() if d.platform == "cpu"]
    if len(devs) < n_dev:
        pytest.skip(f"needs {n_dev} host devices, have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:n_dev]), ("groups",))

    cap = 16
    addrs = {1: f"ul-mesh{n_dev}-1", 2: f"ul-mesh{n_dev}-2",
             3: f"ul-mesh{n_dev}-3"}
    reset_inproc_network()
    old_parity = hp.PARITY
    hp.PARITY = True
    hp.PARITY_FAILURES.clear()
    group = ColocatedEngineGroup(
        capacity=cap, P=5, W=32, M=8, E=4, O=32, budget=4, mesh=mesh
    )
    nhs = {}
    for rid, addr in addrs.items():
        d = f"/tmp/nh-ul-mesh{n_dev}-{rid}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[rid] = NodeHost(NodeHostConfig(
            nodehost_dir=d, rtt_millisecond=5, raft_address=addr,
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=1, apply_shards=2),
                step_engine_factory=group.factory,
            ),
        ))
    try:
        for rid, nh in nhs.items():
            nh.start_replica(
                addrs, False, KVStore,
                Config(replica_id=rid, shard_id=1, election_rtt=20,
                       heartbeat_rtt=2, pre_vote=True, check_quorum=True),
            )
        deadline = time.time() + 30
        leader = None
        while time.time() < deadline and leader is None:
            leader = next(
                (r for r, nh in nhs.items() if nh.is_leader_of(1)), None
            )
            time.sleep(0.02)
        assert leader, "no leader within 30s"
        nh = nhs[leader]
        for i in range(12):
            nh.sync_propose(
                nh.get_noop_session(1),
                set_cmd(f"m{i}", f"v{i}".encode()), timeout=20.0,
            )
        core = group.core
        assert core.stats.get("launches", 0) > 0
        assert core.stats.get("divergence_halts", 0) == 0
        assert hp.PARITY_FAILURES == [], hp.PARITY_FAILURES[:3]
        per = placement.rows_per_device(cap, n_dev)
        with core._lock:
            # slices tile the block (zero-copy views)
            parts = [
                core._ulanes.device_slice(d, n_dev) for d in range(n_dev)
            ]
            assert np.array_equal(
                np.concatenate(parts, axis=1), core._ulanes.words
            )
            n_res = 0
            for (sid, rid), g in core._row_of.items():
                meta = core._meta.get(g)
                if meta is None:
                    continue
                n_res += 1
                d = placement.device_of_row(g, cap, n_dev)
                assert d == core.device_coordinate(sid, rid), (sid, rid)
                sl = core._ulanes.device_slice(d, n_dev)
                # the row's lane column is addressable THROUGH its
                # device's slice, and it mirrors the scalar raft
                r = meta.node.peer.raft
                assert sl[R_TERM, g - d * per] == r.term, (sid, rid)
            assert n_res > 0, "no device-resident rows"
    finally:
        hp.PARITY = old_parity
        for nh in nhs.values():
            try:
                nh.close()
            except Exception:  # noqa: BLE001
                pass


class TestLaneSlotPersistReadback:
    """InMemLogDB columnar hard-state lanes: the persist half
    (``save_state_slots``) and the reader half (``read_raft_state``
    via ``_hs_sync``) must compose for replicas that have ONLY ever
    saved through the lane path — such a replica has no classic node
    store yet, and an early-return on that miss read its durable lane
    words back as None (the PR-15 db-parity rot, since fixed)."""

    def test_lane_only_replica_reads_back(self):
        from dragonboat_tpu.storage.logdb import InMemLogDB

        db = InMemLogDB()
        s = db.state_lane_slot(7, 3)
        db.save_state_slots(
            np.array([s]), np.array([5]), np.array([2]),
            np.array([11]), worker_id=0,
        )
        rs = db.read_raft_state(7, 3, 0)
        assert rs is not None, "lane-only hard state must be readable"
        st = rs.state
        assert (st.term, st.vote, st.commit) == (5, 2, 11)
        # the lazy materialize is exactly-once and stable: a second
        # read (dirty bit now clear) returns the same words
        st2 = db.read_raft_state(7, 3, 0).state
        assert (st2.term, st2.vote, st2.commit) == (5, 2, 11)

    def test_registered_but_never_saved_slot_reads_none(self):
        from dragonboat_tpu.storage.logdb import InMemLogDB

        db = InMemLogDB()
        db.state_lane_slot(7, 4)  # registered, nothing persisted
        assert db.read_raft_state(7, 4, 0) is None

    def test_lane_words_win_over_stale_classic_state(self):
        from dragonboat_tpu.pb import State, Update
        from dragonboat_tpu.storage.logdb import InMemLogDB

        db = InMemLogDB()
        db.save_raft_state(
            [Update(shard_id=7, replica_id=5,
                    state=State(term=1, vote=1, commit=1))],
            worker_id=0,
        )
        s = db.state_lane_slot(7, 5)
        db.save_state_slots(
            np.array([s]), np.array([9]), np.array([3]),
            np.array([40]), worker_id=0,
        )
        st = db.read_raft_state(7, 5, 0).state
        assert (st.term, st.vote, st.commit) == (9, 3, 40)
