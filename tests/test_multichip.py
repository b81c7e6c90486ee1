"""Multi-chip device plane (ISSUE 12 / ROADMAP 3 / docs/MULTICHIP.md).

Sharded-vs-single-device BIT-EXACT parity over the forced-host-device
mesh the suite already runs under (conftest forces 8 CPU devices):

* the shard_map'd kernel step (``kernel.make_step_sharded``) against
  ``kernel.step`` on the same global rows;
* the full sharded consensus round (``route.make_sharded_round`` —
  per-device step + intra-device routing + the ppermute collective
  exchange lane) against ``route.routed_round``, at 2, 4 and 8
  devices, over a mixed election/commit script in a REPLICA-MAJOR
  layout where every group's replicas straddle device blocks, so the
  parity covers genuine cross-device routed messages;
* a membership-change fence: peer tables mutate at a round boundary
  (the kernel-loop analogue of the colocated pipeline fence — both
  paths apply the change between launches), parity must hold across
  it;
* the jaxcheck transfer/dtype audit over the sharded entry points
  (``registry.mesh_entry_points``) — zero host transfers in the
  steady sharded loop;
* the raftlint ``mesh-loop`` rule fixture;
* the balance planner's chip-capacity dimension and the device-lease
  evidence lanes (hostplane.LeaseLanes), which are host-only.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dragonboat_tpu.ops import route as R
from dragonboat_tpu.ops.kernel import make_step_sharded
from dragonboat_tpu.ops.types import (
    MT_TICK,
    ROLE_LEADER,
    make_inbox,
    make_state,
)

REPL = 3


def _mesh(n):
    devs = [d for d in jax.devices() if d.platform == "cpu"]
    if len(devs) < n:
        pytest.skip(f"needs {n} host devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), ("groups",))


def _replica_major(groups, P):
    """Group i's replicas at rows {i, groups+i, 2*groups+i}: at any
    mesh size > 1 every group straddles device blocks, so all raft
    traffic rides the collective lane."""
    G = groups * REPL
    shard_ids = np.tile(np.arange(1, groups + 1, dtype=np.int32), REPL)
    replica_ids = np.repeat(np.arange(1, REPL + 1, dtype=np.int32), groups)
    peer_ids = np.broadcast_to(
        np.arange(1, REPL + 1, dtype=np.int32), (G, P)
    ).copy()
    return G, shard_ids, replica_ids, peer_ids


def _assert_tree_equal(a, b, what):
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert np.array_equal(x, y), (
            f"{what}.{f} diverged at {np.argwhere(x != y)[:5].tolist()}"
        )


def test_sharded_step_parity():
    """make_step_sharded == step, bit for bit, over an election-heavy
    fused-tick script (single-voter + 3-replica rows)."""
    mesh = _mesh(4)
    G, P, W, M, E, O = 32, 3, 8, 4, 1, 8
    replica_ids = np.ones((G,), np.int32)
    peer_ids = np.zeros((G, P), np.int32)
    peer_ids[: G // 2, 0] = 1
    peer_ids[G // 2:, :3] = np.array([1, 2, 3], np.int32)
    st = make_state(
        G, P, W,
        shard_ids=np.arange(1, G + 1, dtype=np.int32),
        replica_ids=replica_ids, peer_ids=peer_ids,
        election_timeout=6, heartbeat_timeout=2,
    )
    ib = make_inbox(G, M, E)
    ib = ib._replace(
        mtype=ib.mtype.at[:, :].set(MT_TICK),
        log_index=ib.log_index.at[:, :].set(3),  # fused count 3/slot
    )
    from dragonboat_tpu.ops.kernel import step

    step_single = jax.jit(functools.partial(step, out_capacity=O))
    step_shard = make_step_sharded(mesh, out_capacity=O)
    sa, sb = st, st
    for _ in range(4):
        sa, oa = step_single(sa, ib)
        sb, ob = step_shard(sb, ib)
    _assert_tree_equal(sa, sb, "state")
    _assert_tree_equal(oa, ob, "out")
    # the script actually elects: single-voter rows all lead
    assert (np.asarray(sb.role)[: G // 2] == ROLE_LEADER).all()


def _run_round_parity(n_dev, groups=8, rounds=24, mutate_at=None):
    mesh = _mesh(n_dev)
    P, W, E, O, BUD, BASE = 3, 16, 2, 16, 4, 2
    M = BASE + P * BUD
    G, shard_ids, replica_ids, peer_ids = _replica_major(groups, P)
    assert G % n_dev == 0
    tabs = R.build_route_tables_mesh(shard_ids, replica_ids, peer_ids, n_dev)
    XB = R.xbudget_for(tabs, BUD, n_dev)
    dest, rank = R.build_route_tables(shard_ids, replica_ids, peer_ids)
    st = make_state(
        G, P, W, shard_ids=shard_ids, replica_ids=replica_ids,
        peer_ids=peer_ids, election_timeout=10, heartbeat_timeout=2,
    )
    ib = R.make_prefill(st, M, E)
    round_single = jax.jit(functools.partial(
        R.routed_round, out_capacity=O, budget=BUD, base=BASE,
        propose_leaders=True,
    ))
    round_shard = R.make_sharded_round(
        mesh, M=M, E=E, out_capacity=O, budget=BUD, xbudget=XB,
        base=BASE, propose_leaders=True,
    )
    args_s = [jnp.asarray(t) for t in (tabs.dest_local, tabs.dest_dev,
                                       tabs.rank_in_dest)]
    args_r = [jnp.asarray(dest), jnp.asarray(rank)]
    st_r = st_s = st
    ib_r = ib_s = ib
    lane_tot = np.zeros((7,), np.int64)
    for i in range(rounds):
        if mutate_at is not None and i == mutate_at:
            # membership-change FENCE: the change applies at a round
            # boundary on BOTH paths (the colocated engine drains its
            # pipeline to depth 0 before mutating membership — same
            # contract, kernel-loop shape).  Group 1 drops replica 3:
            # peer slot cleared on every row, tables rebuilt.
            peer_ids[shard_ids == 1, 2] = 0

            def drop(stx):
                pid = np.array(np.asarray(stx.peer_id))
                pid[shard_ids == 1, 2] = 0
                return stx._replace(peer_id=jnp.asarray(pid))

            st_r, st_s = drop(st_r), drop(st_s)
            tabs2 = R.build_route_tables_mesh(
                shard_ids, replica_ids, peer_ids, n_dev
            )
            dest2, rank2 = R.build_route_tables(
                shard_ids, replica_ids, peer_ids
            )
            args_s = [jnp.asarray(t) for t in (
                tabs2.dest_local, tabs2.dest_dev, tabs2.rank_in_dest
            )]
            args_r = [jnp.asarray(dest2), jnp.asarray(rank2)]
        st_r, ib_r, _stats, _n = round_single(st_r, ib_r, *args_r)
        st_s, ib_s, _sstats, lane = round_shard(st_s, ib_s, *args_s)
        lane_tot += np.asarray(lane, np.int64).sum(0)
    _assert_tree_equal(st_r, st_s, "state")
    _assert_tree_equal(ib_r, ib_s, "inbox")
    return st_s, lane_tot, groups


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_sharded_round_parity_cross_device(n_dev):
    st, lane, groups = _run_round_parity(n_dev)
    # real cross-device routed messages flowed, none were lane-dropped
    assert lane[1] > 0, "no cross-device traffic reached the lane"
    assert lane[3] == 0, f"xlane drops at sized budget: {lane}"
    # consensus actually advanced through the lane: elections + commits
    commits = np.asarray(st.committed).reshape(REPL, groups).max(0)
    assert (np.asarray(st.role) == ROLE_LEADER).sum() >= groups - 2
    assert (commits > 0).sum() >= groups - 2


def test_membership_change_fence():
    """Parity holds across a mid-run membership change applied at the
    round-boundary fence, and the removed replica's group keeps
    committing with the shrunken voter set."""
    st, lane, groups = _run_round_parity(4, rounds=30, mutate_at=12)
    assert lane[1] > 0
    commits = np.asarray(st.committed).reshape(REPL, groups).max(0)
    assert commits[0] > 0  # the mutated group still commits


def test_sharded_entry_points_transfer_free():
    """jaxcheck transfer + dtype rules over the sharded programs: zero
    host transfers inside the steady sharded loop (tracing only — no
    compile, so this is cheap at the canonical geometry)."""
    from dragonboat_tpu.analysis import jaxcheck
    from dragonboat_tpu.ops import registry as REG

    mesh = _mesh(2)
    findings = jaxcheck.audit(entries=REG.mesh_entry_points(mesh))
    assert not findings, [f.render() for f in findings]


def test_mesh_loop_lint_rule():
    from dragonboat_tpu.analysis.raftlint import lint_source

    bad = (
        "def launch(xs):  # mesh-hot\n"
        "    for d in jax.devices():\n"
        "        jax.device_put(xs, d)\n"
    )
    finds = lint_source(bad, "dragonboat_tpu/ops/route.py")
    rules = [f.rule for f in finds]
    assert rules.count("mesh-loop") == 2, finds
    ok = (
        "def launch(xs):  # mesh-hot\n"
        "    for shift in range(1, 8):\n"
        "        xs = xs + shift\n"
        "    return xs\n"
    )
    assert not [
        f for f in lint_source(ok, "dragonboat_tpu/ops/route.py")
        if f.rule == "mesh-loop"
    ]
    # out of scope: unmarked functions and non-ops modules stay silent
    assert not [
        f for f in lint_source(bad, "dragonboat_tpu/gateway/router.py")
        if f.rule == "mesh-loop"
    ]


def test_planner_chip_capacity_dimension():
    """An 8-chip host absorbs ~8x the replicas of 1-chip hosts; chips
    omitted → byte-identical to the unweighted planner."""
    from dragonboat_tpu.balance.planner import Planner
    from dragonboat_tpu.balance.view import ClusterView, ShardView

    def view(chips):
        shards = tuple(
            ShardView(
                shard_id=s,
                members=((1, "big"),),
                replicas=(),
                next_replica_id=2,
            )
            for s in range(1, 19)
        )
        return ClusterView(
            hosts=("big", "small1", "small2"), draining=(),
            shards=shards, chips=chips,
        )

    pl = Planner(seed=1, replication_factor=1)
    # unweighted: 18 replicas spread 6/6/6
    plan = pl.plan(view(()))
    moved = sum(1 for m in plan if m.kind == "replace")
    assert moved == 12, plan.describe()
    # big host has 8 chips: per-chip balance keeps most replicas on it
    plan_w = pl.plan(view((("big", 8),)))
    moved_w = sum(1 for m in plan_w if m.kind == "replace")
    assert moved_w < moved, (
        f"chip weighting did not reduce off-big moves: {moved_w}"
    )
    # determinism: same view + seed -> byte-identical plan
    assert plan_w.describe() == pl.plan(view((("big", 8),))).describe()
    # HOMOGENEOUS multi-chip fleet: equal chips (any value) must spread
    # exactly like the unweighted planner — the cross-multiplied stop
    # condition once tolerated a `chips`-wide skew between identical
    # 8-chip hosts (review finding)
    eq = view((("big", 8), ("small1", 8), ("small2", 8)))
    assert pl.plan(eq).describe() == plan.describe()


def test_lease_lanes_window_model():
    """hostplane.LeaseLanes: first window never anchors (fabricated
    become-leader actives); after an observed crossing, the
    quorum-active flag anchors at the window start; crossings reset."""
    from dragonboat_tpu.ops.hostplane import LeaseLanes
    from dragonboat_tpu.ops.types import F_QUORUM_ACTIVE

    ll = LeaseLanes(4)
    g, et = 2, 10
    ll.arm(g, et, 0)
    now = 100
    # first window: flag up but no crossing observed yet -> no anchor
    assert ll.row_step(g, 4, now, F_QUORUM_ACTIVE) == -1
    # crossing at el 4+6 >= 10: window starts at `now`, still no anchor
    now += 6
    assert ll.row_step(g, 6, now, F_QUORUM_ACTIVE) == -1
    ws = now
    # mid-window with the flag: anchors at the window start
    now += 4
    assert ll.row_step(g, 4, now, F_QUORUM_ACTIVE) == ws
    # flag down -> no anchor; disarm kills the model
    now += 1
    assert ll.row_step(g, 1, now, 0) == -1
    ll.disarm(g)
    assert ll.row_step(g, 5, now, F_QUORUM_ACTIVE) == -1


def test_quorum_active_flag_device_side():
    """engine._summarize_flags sets F_QUORUM_ACTIVE exactly for
    CheckQuorum voter-leaders whose active voter lanes reach quorum."""
    from dragonboat_tpu.ops.engine import _summarize_flags
    from dragonboat_tpu.ops.kernel import step
    from dragonboat_tpu.ops.types import F_QUORUM_ACTIVE, make_out

    G, P, W = 4, 3, 8
    peer_ids = np.broadcast_to(
        np.array([1, 2, 3], np.int32), (G, P)
    ).copy()
    st = make_state(
        G, P, W,
        shard_ids=np.arange(1, G + 1, dtype=np.int32),
        replica_ids=np.ones((G,), np.int32), peer_ids=peer_ids,
        election_timeout=10, heartbeat_timeout=2, check_quorum=True,
    )
    role = np.asarray(st.role).copy()
    active = np.asarray(st.active).copy()
    role[0] = role[1] = role[2] = ROLE_LEADER
    active[0] = [1, 1, 0]   # self + one voter = quorum of 3 -> set
    active[1] = [1, 0, 0]   # self only -> below quorum
    # row 2: leader but check_quorum off
    cq = np.asarray(st.check_quorum).copy()
    cq[2] = 0
    active[2] = [1, 1, 1]
    st2 = st._replace(
        role=jnp.asarray(role), active=jnp.asarray(active),
        check_quorum=jnp.asarray(cq),
    )
    out = make_out(G, P, 4, 2, 8)
    flags = np.asarray(_summarize_flags(st2, st2, out))
    assert flags[0] & F_QUORUM_ACTIVE
    assert not flags[1] & F_QUORUM_ACTIVE
    assert not flags[2] & F_QUORUM_ACTIVE
    assert not flags[3] & F_QUORUM_ACTIVE  # follower
    del step  # imported for registry warm parity only


def test_anchor_quorum_evidence():
    """Raft.anchor_quorum_evidence raises the voting remotes'
    last_resp_tick floor monotonically and only on leaders, and
    quorum_responded_tick picks the anchor up."""
    from raft_harness import Network

    net = Network.of(3, check_quorum=True)
    net.elect(1)
    r = net.peers[1]
    base = r.quorum_responded_tick()
    anchor = r.tick_count + 5  # a fresher device-window start
    r.anchor_quorum_evidence(anchor)
    assert r.quorum_responded_tick() >= anchor > base
    # monotone: an older anchor never regresses the evidence
    r.anchor_quorum_evidence(anchor - 3)
    assert r.quorum_responded_tick() >= anchor
    # non-leader: no-op
    f = net.peers[2]
    before = {
        pid: rm.last_resp_tick for pid, rm in f.all_remotes().items()
    }
    f.anchor_quorum_evidence(10_000)
    assert before == {
        pid: rm.last_resp_tick for pid, rm in f.all_remotes().items()
    }


def test_device_lease_reads_colocated():
    """ROADMAP 4b end to end: a device-RESIDENT CheckQuorum leader
    holds a positive, window-bounded lease (the F_QUORUM_FRESH flag ->
    hostplane.LeaseAges -> Node.lease_probe, renewed every launch since
    PR 31), so gateway lease reads stay on device-hosted shards instead
    of falling back to ReadIndex — and holds it CONTINUOUSLY, which the
    window form (one anchor a CheckQuorum window) never could.  Also
    pins the clock-lockstep invariant: the device tick tail advances
    the scalar raft's logical clock (a frozen r.tick_count overstated
    the lease by the whole residency)."""
    import shutil
    import time

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
    from dragonboat_tpu.transport.inproc import reset_inproc_network
    from test_nodehost import KVStore, set_cmd

    addrs = {1: "mc-lease-1", 2: "mc-lease-2", 3: "mc-lease-3"}
    reset_inproc_network()
    group = ColocatedEngineGroup(
        capacity=16, P=5, W=32, M=8, E=4, O=32, budget=4
    )
    nhs = {}
    for rid, addr in addrs.items():
        d = f"/tmp/nh-mc-lease-{rid}"
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
        nh.sync_propose(
            nh.get_noop_session(1), set_cmd("k", "v"), timeout=20.0
        )
        node = nh._nodes[1]
        best, n_pos = 0, 0
        deadline = time.time() + 45
        while time.time() < deadline:
            lt = node.lease_remaining_ticks()
            best = max(best, lt)
            n_pos += lt > 2
            if n_pos > 10 and group.core.stats["device_steps"] > 30:
                break
            time.sleep(0.05)
        r = node.peer.raft
        assert group.core._row_of.get((1, leader)) is not None, (
            "leader row left the device"
        )
        # once held, held: a healthy leader's lease is renewed by every
        # launch a quorum answers in, so it never saw-tooths to the
        # margin between two CheckQuorum sweeps
        samples = []
        for _ in range(200):
            samples.append(node.lease_remaining_ticks())
            time.sleep(0.005)
        assert sum(lt > 2 for lt in samples) >= 190, sorted(samples)[:20]
        # the evidence is the engine's: the node holds its row's cell,
        # and no scalar remote was anchored to get here
        assert node.lease_cell is not None
        assert node.lease_cell[0] is group.core._lease
        assert group.core.stats["lease_rows_armed"] > 0
        # ONE lease pass per merged generation: the dev_ok merge path
        # once ran _lease_pass twice (review finding), feeding tick_fed
        # twice and halving the modeled CheckQuorum window period
        core = group.core
        steps0 = core.stats["device_steps"]
        calls = [0]
        orig = core._lease_pass

        def counting(*a, **k):
            calls[0] += 1
            return orig(*a, **k)

        core._lease_pass = counting
        deadline = time.time() + 20
        while (
            core.stats["device_steps"] - steps0 < 10
            and time.time() < deadline
        ):
            time.sleep(0.05)
        core._lease_pass = orig
        steps = core.stats["device_steps"] - steps0
        assert steps >= 10, "engine idled during the lease-pass window"
        # <= launches + pipeline slack: merges never outnumber launches,
        # and a double-pass would show ~2x here
        assert calls[0] <= steps + 4, (calls[0], steps)
        # positive AND window-bounded: an anchor can never claim more
        # than one election window of lease
        assert 2 < best <= r.election_timeout, best
        assert n_pos > 10, "lease not held continuously"
        # clock lockstep (the overstated-lease bug class)
        assert r.tick_count == node.tick_count
    finally:
        for nh in nhs.values():
            try:
                nh.close()
            except Exception:  # noqa: BLE001
                pass


def test_mesh_tables_and_xbudget():
    G, shard_ids, replica_ids, peer_ids = _replica_major(8, 3)
    tabs = R.build_route_tables_mesh(shard_ids, replica_ids, peer_ids, 4)
    dest, rank = R.build_route_tables(shard_ids, replica_ids, peer_ids)
    gl = G // 4
    placed = dest >= 0
    assert np.array_equal(tabs.dest_dev[placed], dest[placed] // gl)
    assert np.array_equal(tabs.dest_local[placed], dest[placed] % gl)
    assert np.array_equal(tabs.rank_in_dest, rank)
    assert (tabs.dest_dev[~placed] == -1).all()
    # worst-case sizing: every remote peer slot times the budget
    xb = R.xbudget_for(tabs, 4, 4)
    assert xb >= 4
    with pytest.raises(ValueError):
        R.build_route_tables_mesh(shard_ids, replica_ids, peer_ids, 5)


def test_engine_warm_set_covers_proposal_launch_on_mesh():
    """The product engine's ``mesh=`` path: the launch's one program
    (``_host_inbox``) runs on a replicated upload, and its outputs take
    the shardings the compiler chose for that bucket's program, so the
    step over a launch that carried dense rows (the first launch with a
    proposal) may key its own executable under a mesh.  ``chip_smoke.py
    --chips 4`` met it on the v5e as a 17 s compile inside the first
    write (PR 21), when two programs built that inbox; ``_warm()`` must
    have compiled every bucket's, and the step over each."""
    from dragonboat_tpu.analysis import jitcheck
    from dragonboat_tpu.ops import colocated as C
    from dragonboat_tpu.ops import sync as S

    G, M, E, O = 16, 8, 2, 16
    core = C.ColocatedVectorEngine(
        capacity=G, P=3, W=16, M=M, E=E, O=O, budget=4, mesh=_mesh(4)
    )
    sentry = jitcheck.Sentry()
    sentry.mark()
    for nsb in (0, 1, 2, 4, 8, 16):
        flat = np.zeros((5 * G + nsb * S.inbox_row_ints(M, E),), np.int32)
        combo, host = C._host_inbox(core._put(flat), G=G, M=M, E=E, NSB=nsb)
        new_state, out = C._assemble_and_step(
            core._state, host, core._pending, combo, out_capacity=O)
        C._route_step(
            core._state, new_state, out,
            core._put_rows(np.full((G, 3), -1, np.int32)),
            core._put_rows(np.zeros((G, 3), np.int32)),
            combo, PB=3 * 4, E=E, budget=4,
        )
        # rounds 2..K of a wave read the resident empty inbox
        C._assemble_and_step(core._state, core._zero_host, core._pending,
                             combo, out_capacity=O)
    assert sentry.retraces() == []
