"""The tick lane through the completion (PR 29): a launch's tick-only
rows stay columns of a ``hostplane.TickLane`` from the plan loop to the
end of ``_complete_generation``; ``rec.batch`` holds the active rows
only, the lease pass and the tick bookkeeping run over the stepped rows
as arrays, and Python walks a row only if it carried input or the
round's flags mark it.  The per-row passes stay as the parity oracle
(``hostplane.PARITY``) and run here beside the array ones.

Two clusters for the module, 8 shards x 3 and 8 shards x 5 replicas
(the geometries tests/test_host_accounting.py and
tests/test_ondisk_served.py compile), each with a simulated link floor
so that generations complete in ``_launch_generation``'s room check.
Counts and relations only: a CPU run tells no time that matters.
"""
import shutil
import threading
import time

import numpy as np
import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.engine.execengine import WorkReady
from dragonboat_tpu.node import Node
from dragonboat_tpu.ops import colocated, hostplane
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.ops.types import F_ANY_LIVE, F_ESC
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import KVStore, propose_r, set_cmd
from test_tick_lane import parity_oracle
from test_vector_engine import read_r

SHARDS = list(range(1, 9))
GEOMS = {
    3: dict(capacity=32, P=3, W=16, M=8, E=4, O=32, budget=4),
    5: dict(capacity=64, P=5, W=16, M=8, E=4, O=32, budget=4),
}


class Cluster:
    def __init__(self, workdir, replicas):
        reset_inproc_network()
        self.addrs = {r: f"lanec{replicas}-nh-{r}"
                      for r in range(1, replicas + 1)}
        self.group = ColocatedEngineGroup(
            **GEOMS[replicas], pipeline_depth=2, sync_floor_ms=5.0)
        self.nhs = {}
        for rid, addr in self.addrs.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=f"{workdir}/nh-{rid}",
                rtt_millisecond=5,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=self.group.factory,
                ),
            ))
        self.core = self.group.core
        for s in SHARDS:
            for rid, nh in self.nhs.items():
                nh.start_replica(self.addrs, False, KVStore, Config(
                    replica_id=rid, shard_id=s, election_rtt=20,
                    heartbeat_rtt=2, pre_vote=True, check_quorum=True))
        self.wait_leaders()

    def wait_leaders(self, shards=SHARDS, deadline=60.0):
        end = time.time() + deadline
        while not all(self.nhs[1].get_leader_id(s)[1] for s in shards):
            assert time.time() < end, "no leader on every shard"
            time.sleep(0.05)

    def leader(self, shard):
        return self.nhs[1].get_leader_id(shard)[0]

    def writes_and_a_read(self, tag, shards=SHARDS, n=3):
        for s in shards:
            nh = self.nhs[1 + s % len(self.nhs)]
            sess = nh.get_noop_session(s)
            for i in range(n):
                propose_r(nh, sess, set_cmd(f"{tag}{i}", str(i).encode()))
            via = self.nhs[self.leader(s)]
            assert read_r(via, s, f"{tag}{n - 1}") == str(n - 1).encode()

    def close(self):
        for nh in self.nhs.values():
            nh.close()


@pytest.fixture(scope="module", params=[3, 5], ids=["8x3", "8x5"])
def cluster(request, tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp(f"lanec{request.param}"))
    c = Cluster(workdir, request.param)
    yield c
    c.close()
    shutil.rmtree(workdir, ignore_errors=True)


class Watch:
    """What every completion was handed and what it walked, recorded
    around ``_complete_generation`` (the core lock is held there)."""

    def __init__(self, core):
        self.core, self.recs = core, []
        self.appended, self.cur = {}, None
        self.real_complete = core._complete_generation
        self.real_live = core._live_rows
        self.real_appends = core._merge_appends

    def __enter__(self):
        core = self.core

        def live_rows(rec, flags, sets, esc_seen, esc_other):
            live = self.real_live(rec, flags, sets, esc_seen, esc_other)
            marked = int(((flags[rec.lane.gs_np] & F_ANY_LIVE) != 0).sum())
            self.cur["rounds"].append(
                (len(live), marked, len(sets.live_other)))
            return live

        def merge_appends(r, g, *a, **k):
            self.cur["appended"].add(g)
            return self.real_appends(r, g, *a, **k)

        def complete(rec):
            self.cur = dict(
                n_act=len(rec.batch), n_lane=len(rec.lane),
                n_stepped=len(rec.batch_gs), lane_gs=set(rec.lane.gs),
                wave=rec.rounds, rounds=[], appended=set(),
                active_gs=[g for _, g, _, _ in rec.batch],
                gs_head=rec.batch_gs[:len(rec.batch)].tolist(),
                walked0=core.stats["completion_rows_walked"])
            updates = self.real_complete(rec)
            cur, self.cur = self.cur, None
            cur["walked"] = (
                core.stats["completion_rows_walked"] - cur.pop("walked0"))
            cur["clocked"] = rec.clocked
            row_of = core._row_of
            cur["updates"] = [
                row_of.get((n.shard_id, n.replica_id)) for n, _u in updates]
            self.recs.append(cur)
            return updates

        with core._lock:
            core._live_rows = live_rows
            core._merge_appends = merge_appends
            core._complete_generation = complete
            self.stats0 = dict(core.stats)
        return self

    def __exit__(self, *exc):
        with self.core._lock:
            del self.core._live_rows
            del self.core._merge_appends
            del self.core._complete_generation
            self.stats1 = dict(self.core.stats)


# -- (a) the oracle beside every completion of a seeded schedule ---------
def test_array_and_per_row_passes_agree_through_a_schedule(cluster):
    core = cluster.core
    n_rep = len(cluster.nhs)
    st0 = dict(core.stats)
    with parity_oracle() as failures, Watch(core) as watch:
        cluster.writes_and_a_read("a")
        time.sleep(0.4)  # launches that carry nothing but ticks
        shard = 1
        old = cluster.leader(shard)
        target = 1 + old % n_rep
        cluster.nhs[old].request_leader_transfer(shard, target)
        end = time.time() + 30.0
        while cluster.leader(shard) != target:
            assert time.time() < end, "leadership did not move"
            time.sleep(0.05)
        # a stopped replica: a follower leaves its row while launches
        # are in flight, and its group goes on without it (not on host
        # 1, which answers leader(), nor on the host the writes go by)
        stopped, gone = next(
            (s, r) for s in SHARDS[1:] for r in cluster.nhs
            if r not in (1, 1 + s % n_rep, cluster.leader(s)))
        cluster.nhs[gone].stop_shard(stopped)
        cluster.writes_and_a_read("b")
        time.sleep(0.4)
        assert failures() == 0, hostplane.PARITY_FAILURES[:3]
    st1 = dict(core.stats)
    assert st1["launches"] > st0["launches"]
    assert st1["tick_lane_rows"] > st0["tick_lane_rows"]
    assert st1["device_reads"] > st0["device_reads"], (
        "no ReadIndex read went through the device: nothing tested")
    assert st1["divergence_halts"] == 0 and st1["pipeline_resets"] == 0
    assert core._row_of.get((stopped, gone)) is None

    recs = watch.recs
    assert len(recs) > 20 and all(r["clocked"] for r in recs)
    # the in-flight record: batch is the active rows, the stepped set is
    # those first and the lane after
    for r in recs:
        assert r["n_stepped"] == r["n_act"] + r["n_lane"]
        assert r["gs_head"] == r["active_gs"]
        assert not r["lane_gs"] & set(r["active_gs"])
    # (counted at the launch: up to two generations in flight either end)
    assert abs(sum(r["n_lane"] for r in recs) - (
        watch.stats1["tick_lane_rows"] - watch.stats0["tick_lane_rows"]
    )) <= 4 * len(SHARDS) * n_rep
    # what a completion walks: every round the active rows, the lane
    # rows that round's flags mark and the other rows with effects;
    # beside them only the few rows the lease pass arms, starts a
    # window of or anchors (never more than the rows there are)
    for r in recs:
        assert 1 <= len(r["rounds"]) <= r["wave"]
        in_rounds = sum(n for n, _m, _o in r["rounds"])
        for n_live, marked, other in r["rounds"]:
            assert n_live <= r["n_act"] + marked + other + len(r["appended"])
        assert in_rounds <= r["walked"] <= in_rounds + r["n_stepped"] + 8
    # and in the launches that carried nothing but ticks nearly nothing
    quiet = [r for r in recs if r["n_act"] == 0 and r["n_lane"] >= 8]
    assert len(quiet) > 5
    assert sum(r["walked"] for r in quiet) < 0.5 * sum(
        r["n_lane"] for r in quiet)
    assert (watch.stats1["completion_rows_walked"]
            - watch.stats0["completion_rows_walked"]
            == sum(r["walked"] for r in recs))


# -- (b) a lane row with a routed append in the same launch --------------
def test_a_lane_row_that_a_routed_append_reaches_emits_one_update(cluster):
    core = cluster.core

    def lane_rows_appended(recs):
        # a follower fed nothing but its tick, whose leader's append
        # reached it in a later round of the same wave
        return [(r, g) for r in recs for g in r["appended"] & r["lane_gs"]]

    with Watch(core) as watch:
        # on a loaded box most appends land on rows no worker stepped in
        # that launch (`live_other`): write until one lands on the lane
        end = time.time() + 60.0
        for k in range(40):
            cluster.writes_and_a_read(f"c{k}-", shards=SHARDS[2:5], n=4)
            if lane_rows_appended(watch.recs) or time.time() > end:
                break
        time.sleep(0.2)
    hit = lane_rows_appended(watch.recs)
    assert hit, "no routed append landed on a lane row: nothing tested"
    for r, g in hit:
        assert r["updates"].count(g) == 1, (g, r["updates"])
    for r in watch.recs:
        for g in set(r["updates"]):
            if g is not None:
                assert r["updates"].count(g) == 1, (g, r["updates"])


# -- (c) the slow paths get a row's inputs back --------------------------
def test_a_lane_row_that_escalates_replays_its_ticks(cluster):
    core = cluster.core
    st0 = dict(core.stats)
    seen, replayed = {}, []
    real_complete = core._complete_generation
    real_head = core._parse_head
    real_step = Node.step_with_inputs

    def complete(rec):
        if not seen and len(rec.lane) and not rec.batch:
            # the first lane row of a launch that carried only ticks
            seen.update(g=rec.lane.gs[0], node=rec.lane.nodes[0],
                        ticks=rec.lane.ticks[0], pos=len(rec.batch),
                        armed=True)
        try:
            return real_complete(rec)
        finally:
            seen["armed"] = False

    def parse_head(head, caps, G, nw):
        out = real_head(head, caps, G, nw)
        if seen.get("armed"):
            # the wave's first round, as the device would report it
            seen["armed"] = False
            flags = np.array(out[0])
            flags[seen["g"]] |= F_ESC
            out = (flags,) + tuple(out[1:])
        return out

    def step_with_inputs(node, si):
        replayed.append((node, si.ticks, si.gc_ticks, len(si.received),
                         len(si.proposals)))
        return real_step(node, si)

    mp = pytest.MonkeyPatch()
    mp.setattr(Node, "step_with_inputs", step_with_inputs)
    with core._lock:
        core._complete_generation = complete
        core._parse_head = parse_head
    try:
        end = time.time() + 20.0
        while not seen or seen["armed"] or not any(
                n is seen["node"] for n, *_ in replayed):
            assert time.time() < end, "no tick-only launch escalated"
            time.sleep(0.02)
    finally:
        with core._lock:
            del core._complete_generation
            del core._parse_head
        mp.undo()
    st1 = dict(core.stats)
    assert st1["escalations"] == st0["escalations"] + 1
    assert st1.get("evict_escalation", 0) == (
        st0.get("evict_escalation", 0) + 1)
    mine = [t for t in replayed if t[0] is seen["node"]]
    # its ticks and nothing else, made into a StepInputs for that row
    assert mine[0][1:] == (seen["ticks"], 0, 0, 0)
    assert st1["divergence_halts"] == 0 and st1["pipeline_resets"] == 0
    cluster.writes_and_a_read("d", shards=[seen["node"].shard_id])


def test_a_pipeline_reset_with_lane_rows_in_flight_loses_no_tick(cluster):
    core = cluster.core
    with core._lock:
        core._fence()
        alive = np.nonzero(core._lanes.alive_mask())[0].tolist()
        assert len(alive) >= 6
        lane = hostplane.TickLane()
        for k, g in enumerate(alive[:5]):
            lane.add(core._meta[g].node, g, fed=1 + k % 2, ticks=1 + k % 3,
                     gc=5 if k == 3 else 0)
        lane.seal()
        node_a = core._meta[alive[5]].node
        batch = [(node_a, alive[5],
                  colocated.StepInputs(ticks=2, gc_ticks=1), [("tick", 2)])]
        nodes = lane.nodes + [node_a]
        before = [(n.tick_count, n.peer.raft.tick_count) for n in nodes]
        resets = core.stats["pipeline_resets"]
        core._inflight.append(colocated._InFlightGen(
            batch=batch, lane=lane, staging={}, alive_np=None,
            batch_gs=np.asarray([alive[5]] + lane.gs, np.int64),
            fed=np.asarray([2] + lane.fed, np.int64), prop_gs=None,
            caps=None, merged=[], out=[], head_dev=[], detail_dev=[],
            t_req=time.monotonic()))
        core._reset_after_pipeline_failure()
        after = [(n.tick_count, n.peer.raft.tick_count) for n in nodes]
        assert not core._inflight
        assert core.stats["pipeline_resets"] == resets + 1
        core.stats["pipeline_resets"] = resets  # the cluster's own: none
    want = lane.clock_np.tolist() + [3]
    assert want == [1, 2, 3, 6, 2, 3]
    assert [(a[0] - b[0], a[1] - b[1]) for a, b in zip(after, before)] == [
        (t, t) for t in want]
    # every row re-uploads from its scalar state and the groups go on
    cluster.writes_and_a_read("e", shards=SHARDS[5:7])


# -- (d) the wake --------------------------------------------------------
def test_the_wake_reaches_the_shards_the_per_row_loop_reached(cluster):
    core = cluster.core
    me = threading.get_ident()
    calls = []

    def notify(self, shard_id):
        if threading.get_ident() == me:
            calls.append((id(self), shard_id))
        else:
            real_notify(self, shard_id)

    def notify_all(self, shard_ids):
        if threading.get_ident() == me:
            assert not isinstance(shard_ids, np.ndarray)
            calls.append((id(self), tuple(shard_ids)))
        else:
            real_all(self, shard_ids)

    real_notify, real_all = WorkReady.notify, WorkReady.notify_all
    mp = pytest.MonkeyPatch()
    with core._lock:
        mp.setattr(WorkReady, "notify", notify)
        mp.setattr(WorkReady, "notify_all", notify_all)
        try:
            # the per-row loop, as every completion ran it before PR 29
            for g in np.nonzero(core._lanes.alive_mask())[0].tolist():
                meta = core._meta.get(g)
                if meta is not None and meta.node.notify_work is not None:
                    meta.node.notify_work()
            per_row, calls = calls, []
            core._wake_alive()
            batched = calls
        finally:
            mp.undo()
    assert len(per_row) >= 3 * (len(SHARDS) - 1)
    want = {}
    for ready, shard in per_row:
        want.setdefault(ready, set()).add(shard)
    got = {}
    for ready, shards in batched:
        assert ready not in got, "more than one call a member NodeHost"
        assert len(set(shards)) == len(shards)
        got[ready] = set(shards)
    assert got == want
    assert len(batched) <= len(cluster.nhs) < len(per_row)
    assert set(got) == {id(nh.engine.step_ready)
                        for nh in cluster.nhs.values()}


# -- (e) the oracle's own comparisons ------------------------------------
NONE = hostplane.LEASE_NONE


def _trace(**over):
    rows = np.asarray([3, 5, 9], np.int64)
    base = dict(
        emitted=frozenset({3, 9}), rows=rows,
        et=np.asarray([20, 0, 20]), age=np.asarray([4, NONE, 9]),
        since=np.asarray([4, NONE, 3]),
        clocks={3: (140, 140), 5: (139, 139), 9: (131, 131)})
    base.update(over)
    return hostplane.CompletionTrace(**base)


@pytest.mark.parametrize("field, value, names", [
    ("emitted", frozenset({3}), "rows emitted"),
    ("rows", np.asarray([3, 5, 8], np.int64), "lease rows"),
    ("et", np.asarray([20, 20, 20]), "lease et"),
    ("age", np.asarray([4, NONE, 3]), "lease age"),
    ("age", np.asarray([NONE, NONE, 9]), "lease age"),
    ("since", np.asarray([4, NONE, NONE]), "lease since"),
    ("since", np.asarray([5, NONE, 3]), "lease since"),
    ("clocks", {3: (140, 139), 5: (139, 139), 9: (131, 131)}, "clocks"),
])
def test_the_completion_oracle_names_what_differs(field, value, names):
    hostplane.assert_completion_parity(_trace(), _trace())
    with pytest.raises(hostplane.HostPlaneParityError, match=names):
        hostplane.assert_completion_parity(_trace(**{field: value}), _trace())
    before = hostplane.PARITY_FAILURE_COUNT
    hostplane.check_completion_parity(_trace(**{field: value}), _trace())
    assert hostplane.PARITY_FAILURE_COUNT == before + 1
    hostplane.PARITY_FAILURE_COUNT = before
    hostplane.PARITY_FAILURES.clear()


def _groups_of_four(G):
    """A route table for ``G`` rows in shards of four resident
    replicas: every row's peers are its shard's rows, itself among
    them, as ``build_route_tables`` lays a colocated shard out."""
    rows = np.arange(G)
    return (rows[:, None] // 4) * 4 + np.arange(4)[None, :]


# no stepped row's host slots were quiet: every one held a message
LOUD = np.zeros((64,), bool)


def test_the_array_lease_step_is_row_step_over_every_row():
    """``LeaseAges.lanes_step`` against ``lease_rows_step`` a row, over
    seeded launches of 64 rows in shards of four: the same lanes on
    every row of the engine, stepped or not; a row fed ticks with the
    flag up is exactly those ticks old on its own clock and as old as
    the peer fed most in that launch on the lane the probe reads, a row
    stepped without a tick anchors nothing until its next feed, and a
    flag that comes up in a later launch anchors the row at the feed it
    belongs to."""
    from dragonboat_tpu.ops.types import F_QUORUM_ACTIVE, F_QUORUM_FRESH

    rng = np.random.default_rng(31)
    G = 64
    a, b = hostplane.LeaseAges(G, 4), hostplane.LeaseAges(G, 4)
    peers = _groups_of_four(G)
    for lanes in (a, b):
        lanes.set_peers(peers)
        for g in range(0, G, 2):
            lanes.arm(g, 20)
    n_armed = n_fresh = n_late = n_ahead = 0
    for launch in range(200):
        gs = np.sort(rng.choice(G, size=40, replace=False)).astype(np.int64)
        fed = rng.integers(0, 4, size=40).astype(np.int64)
        clock = fed + (rng.random(40) < 0.1)  # a dropped tick now and then
        # the window bit rides the same word and anchors nothing here
        flags = (
            np.where(rng.random(G) < 0.6, F_QUORUM_FRESH, 0)
            | np.where(rng.random(G) < 0.5, F_QUORUM_ACTIVE, 0)
        ).astype(np.int32)
        before = a.own.copy()
        hostplane.lease_rows_step(
            b, dict(zip(gs.tolist(), zip(clock.tolist(), fed.tolist()))),
            flags, set())
        armed, fresh, anchored = a.lanes_step(gs, clock, fed, flags,
                                              LOUD[:40])
        for name in ("age", "own", "since", "clk", "mark", "base"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), name
        up = (flags & F_QUORUM_FRESH) != 0
        ticked = (a.et[gs] > 0) & (fed > 0)
        assert armed == ticked.sum() and fresh == (ticked & up[gs]).sum()
        # every row of the engine whose flag is up and whose pending
        # feed is younger than its anchor, stepped or not
        grown = before.copy()
        grown[gs] = np.minimum(before[gs] + clock, NONE)
        assert anchored == (up & (a.since < grown)).sum()
        # fed with the flag up: the anchor is the clock before the feed
        # -- the row's own, and every peer's: the lane reads what the
        # peer fed most has been fed since
        hit = gs[ticked & up[gs]]
        assert np.array_equal(a.own[hit], clock[ticked & up[gs]])
        moved = np.zeros((G,), np.int64)
        moved[gs] = clock
        assert np.array_equal(a.age[hit], moved[peers[hit]].max(axis=1))
        n_ahead += int((a.age[hit] > a.own[hit]).sum())
        # never younger than on the row's own clock
        anchored = a.own < NONE
        assert (a.age[anchored] >= a.own[anchored]).all()
        assert (a.age[~anchored] == NONE).all()
        # stepped with no tick: nothing to anchor at, whatever the flag
        dry = gs[(a.et[gs] > 0) & (fed == 0)]
        assert (a.since[dry] == NONE).all()
        assert (a.own[dry] >= np.minimum(before[dry], NONE)).all()
        # unarmed rows hold nothing
        assert (a.age[1::2] == NONE).all() and (a.since[1::2] == NONE).all()
        rest = np.setdiff1d(np.arange(0, G, 2), gs)
        late = rest[up[rest] & (a.own[rest] < before[rest])]
        assert np.array_equal(a.own[late], a.since[late])
        n_armed += armed
        n_fresh += fresh
        n_late += len(late)
    assert 0 < n_fresh < n_armed and n_late > 0 and n_ahead > 0
    # a disarm forgets the row; its token moved
    tok = int(a.token[2])
    a.disarm(2)
    assert a.age[2] == NONE and a.et[2] == 0 and a.token[2] == tok + 1
    # idle ticks (quiesce, a launch that raised) age a lease, never renew
    a.arm(4, 20)
    a.lanes_step(np.asarray([4]), np.asarray([3]), np.asarray([3]),
                 np.full((G,), F_QUORUM_FRESH, np.int32), LOUD[:1])
    assert a.age[4] == 3
    a.idle(4, 5)
    assert a.age[4] == 8 and a.since[4] == 8
    a.idle(np.asarray([4, 5]), np.asarray([2, 2]))
    assert a.age[4] == 10 and a.age[5] == NONE


NO_FLAGS = np.zeros((8,), np.int32)


def _anchored_leader():
    """Row 0 of a shard of four (rows 0-3), armed and anchored by a
    launch that fed it 3 ticks and its peers 2."""
    from dragonboat_tpu.ops.types import F_QUORUM_FRESH

    lanes = hostplane.LeaseAges(8, 4)
    lanes.set_peers(_groups_of_four(8))
    lanes.arm(0, 20)
    lanes.lanes_step(
        np.arange(4), np.asarray([3, 2, 2, 2]), np.asarray([3, 2, 2, 2]),
        np.where(np.arange(8) == 0, F_QUORUM_FRESH, 0).astype(np.int32),
        LOUD[:4])
    assert lanes.own[0] == 3 and lanes.age[0] == 3
    return lanes


@pytest.mark.parametrize("starved", ["row", "ticker"])
def test_a_leader_that_is_not_stepped_ages_by_its_peers_clocks(starved):
    """The review's case (PR 31): the cut-off leader's row is stepped
    late, or its ticker stands still, while the launches go on feeding
    its peers.  Its own clock stands; the lane the probe reads goes by
    the peer fed most and passes the lease's end with it, before that
    peer may grant a vote."""
    lanes = _anchored_leader()
    for n in range(1, 9):
        if starved == "row":
            # the others' launches, the leader in none of them
            gs, t = np.asarray([1, 2]), np.asarray([2, 1])
        else:
            # stepped with host input, its ticker silent: no tick fed
            gs, t = np.asarray([0, 1, 2]), np.asarray([0, 2, 1])
        lanes.lanes_step(gs, t, t, NO_FLAGS, LOUD[:len(gs)])
        assert lanes.own[0] == 3
        assert lanes.age[0] == 2 + 2 * n  # row 1: 2 in the anchor's launch
    assert lanes.age[0] == 18  # ET - margin: no lease read from here on
    # another shard's clocks are nothing to it
    lanes.lanes_step(np.asarray([5, 6]), np.asarray([9, 9]),
                     np.asarray([9, 9]), NO_FLAGS, LOUD[:2])
    assert lanes.age[0] == 18


@pytest.mark.parametrize("how", ["evicted", "role", "peers", "idle"])
def test_a_peer_the_engine_stops_counting_ends_the_lease(how):
    """What the engine cannot count it does not vouch for: a peer that
    leaves the device (``disarm`` at materialize / release) or changes
    role jumps its clock past any lease AT ONCE, not at the next
    completion; a row whose resident peers change starts over; ticks a
    peer is given outside a completion show at the next one."""
    from dragonboat_tpu.ops.types import F_QUORUM_FRESH

    lanes = _anchored_leader()
    if how == "evicted":
        lanes.disarm(2)
        assert lanes.age[0] >= hostplane.LEASE_GONE > 20
        assert lanes.own[0] == 3  # its own clock says nothing of it
    elif how == "role":
        lanes.arm(1, 20)  # a peer won an election on the device
        assert lanes.age[0] >= hostplane.LEASE_GONE
        assert lanes.age[1] == NONE  # and has no anchor of its own yet
    elif how == "peers":
        table = _groups_of_four(8)
        table[0, 3] = -1  # replica 3 left the shard
        lanes.set_peers(table)
        assert lanes.age[0] == NONE and lanes.own[0] == NONE
        assert lanes.since[0] == NONE
    else:
        lanes.idle(np.asarray([3]), np.asarray([7]))
        assert lanes.age[0] == 3
        lanes.lanes_step(np.asarray([4]), np.asarray([1]), np.asarray([1]),
                         NO_FLAGS, LOUD[:1])
        assert lanes.age[0] == 9
    # a later quorum of answers anchors it again, at clocks read afresh
    gs = np.arange(4)
    lanes.lanes_step(gs, np.full(4, 2), np.full(4, 2),
                     np.where(np.arange(8) == 0, F_QUORUM_FRESH,
                              0).astype(np.int32), LOUD[:4])
    assert lanes.age[0] == 2 and lanes.own[0] == 2


@pytest.mark.parametrize("heard", [False, True])
def test_a_dry_step_keeps_its_pending_anchor_unless_a_message_came(heard):
    """A row stepped with no tick whose host slots held only proposals
    or reads keeps the feed its evidence belongs to, as a row not
    stepped does: the answers that raise its flag afterwards were routed
    and anchor it at that feed.  With a message in its host slots (a
    response may have come through the host) it anchors nothing until
    its next feed.  The per-row twin agrees either way."""
    from dragonboat_tpu.ops.types import F_QUORUM_FRESH

    up = np.where(np.arange(8) == 0, F_QUORUM_FRESH, 0).astype(np.int32)
    lanes = hostplane.LeaseAges(8, 4)
    lanes.set_peers(_groups_of_four(8))
    lanes.arm(0, 20)
    # a tick feed whose answers have not come back: pending at 3
    lanes.lanes_step(np.arange(4), np.asarray([3, 2, 2, 2]),
                     np.asarray([3, 2, 2, 2]), NO_FLAGS, LOUD[:4])
    assert lanes.since[0] == 3 and lanes.own[0] == NONE
    twin = lanes.copy()
    quiet = np.asarray([not heard])
    armed, fresh, anchored = lanes.lanes_step(
        np.asarray([0]), np.asarray([0]), np.asarray([0]), up, quiet)
    hostplane.lease_rows_step(twin, {0: (0, 0)}, up,
                              set() if heard else {0})
    for name in ("age", "own", "since", "clk", "mark", "base"):
        assert np.array_equal(getattr(lanes, name), getattr(twin, name))
    # no feed, so nothing counted fresh; a pending feed anchored late
    assert (armed, fresh, anchored) == (0, 0, 0 if heard else 1)
    if heard:
        assert lanes.since[0] == NONE and lanes.own[0] == NONE
    else:
        # anchored at the feed it belongs to: 3 ticks old on its own
        # clock, as old as its peers fed most since that reading
        assert lanes.own[0] == 3 and lanes.age[0] == 3


def test_the_array_lease_step_is_row_step_with_dry_rows():
    """``lanes_step``'s ``quiet`` against ``lease_rows_step``'s over
    seeded launches in which some rows are stepped with no tick and no
    message."""
    from dragonboat_tpu.ops.types import F_QUORUM_FRESH

    rng = np.random.default_rng(41)
    G = 32
    a, b = hostplane.LeaseAges(G, 4), hostplane.LeaseAges(G, 4)
    for lanes in (a, b):
        lanes.set_peers(_groups_of_four(G))
        for g in range(0, G, 2):
            lanes.arm(g, 20)
    kept = 0
    for _ in range(100):
        gs = np.sort(rng.choice(G, size=20, replace=False)).astype(np.int64)
        fed = rng.integers(0, 3, size=20).astype(np.int64)
        clock = fed + (rng.random(20) < 0.1)
        quiet = rng.random(20) < 0.5
        flags = np.where(rng.random(G) < 0.5, F_QUORUM_FRESH,
                         0).astype(np.int32)
        hostplane.lease_rows_step(
            b, dict(zip(gs.tolist(), zip(clock.tolist(), fed.tolist()))),
            flags, set(gs[quiet].tolist()))
        a.lanes_step(gs, clock, fed, flags, quiet)
        for name in ("age", "own", "since", "clk", "mark", "base"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), name
        kept += int((quiet & (fed == 0) & (a.since[gs] < NONE)).sum())
    assert kept > 0
