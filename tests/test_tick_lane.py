"""The tick lane (PR 27): a launch's tick-only rows reach the encode
phase as two index/count lists and never as ``Message`` objects;
``_encode_rows`` and the lone-tick-or-sparse split walk the active rows
only.  What the device is fed may not differ by a byte from what the
whole batch, encoded row by row, would have fed it.  Since PR 29 the
lane's rows are columns of a ``hostplane.TickLane`` from the plan loop
on and ``batch`` holds the active rows only (what the completion makes
of that: tests/test_tick_lane_completion.py).

One cluster for the module (8 shards x 3 replicas, the geometry
``tests/test_host_accounting.py`` compiles) with a simulated link floor,
so that generations complete in ``_launch_generation``'s room check —
AFTER the plan loop split the batch — and the lane has to give rows
back.  Counts and relations only: a CPU run tells no time that matters.
"""
import contextlib
import shutil
import threading
import time
import types

import numpy as np
import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.ops import colocated, hostplane
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.pb import Entry, Message, MessageType, SystemCtx
from dragonboat_tpu.raft.read_index import ReadIndex
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_host_accounting import GEOM, PHASES
from test_nodehost import KVStore, propose_r, set_cmd
from test_vector_engine import read_r

SHARDS = list(range(1, 9))
ADDRS = {1: "lane-nh-1", 2: "lane-nh-2", 3: "lane-nh-3"}


@contextlib.contextmanager
def parity_oracle():
    """Every launch inside also runs the whole batch through
    ``_encode_rows`` and compares; yields a callable giving the number
    of differences recorded since."""
    old, before = hostplane.PARITY, hostplane.PARITY_FAILURE_COUNT
    hostplane.PARITY = True
    hostplane.PARITY_FAILURES.clear()
    try:
        yield lambda: hostplane.PARITY_FAILURE_COUNT - before
    finally:
        hostplane.PARITY = old


class Cluster:
    def __init__(self, workdir):
        reset_inproc_network()
        self.group = ColocatedEngineGroup(
            **GEOM, pipeline_depth=2, sync_floor_ms=5.0)
        self.nhs = {}
        for rid, addr in ADDRS.items():
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
        self.fresh_engine = dict(self.core.stats)
        for s in SHARDS:
            for rid, nh in self.nhs.items():
                nh.start_replica(ADDRS, False, KVStore, Config(
                    replica_id=rid, shard_id=s, election_rtt=20,
                    heartbeat_rtt=2, pre_vote=True, check_quorum=True))
        self.wait_leaders()

    def wait_leaders(self, deadline=60.0):
        end = time.time() + deadline
        while not all(self.nhs[1].get_leader_id(s)[1] for s in SHARDS):
            assert time.time() < end, "no leader on every shard"
            time.sleep(0.05)

    def leader(self, shard):
        return self.nhs[1].get_leader_id(shard)[0]

    def traffic(self, tag, n=3):
        """Proposals on every shard through a host that may or may not
        lead it, then a ReadIndex read through that host and one through
        the leader's (the one that stays on the device)."""
        for s in SHARDS:
            nh = self.nhs[1 + s % 3]
            sess = nh.get_noop_session(s)
            for i in range(n):
                propose_r(nh, sess, set_cmd(f"{tag}{i}", str(i).encode()))
            for via in (nh, self.nhs[self.leader(s)]):
                assert read_r(via, s, f"{tag}{n - 1}") == str(n - 1).encode()

    def close(self):
        for nh in self.nhs.values():
            nh.close()


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("lane"))
    c = Cluster(workdir)
    yield c
    c.close()
    shutil.rmtree(workdir, ignore_errors=True)


# -- (a) the oracle beside every launch of a live cluster ----------------
def test_lane_and_whole_batch_agree_under_ticks_writes_reads_and_a_leader_change(
        cluster):
    core = cluster.core
    st0 = dict(core.stats)
    with parity_oracle() as failures:
        cluster.traffic("a")
        time.sleep(0.3)  # launches that carry nothing but ticks
        shard = 1
        old = cluster.leader(shard)
        target = 1 + old % 3
        cluster.nhs[old].request_leader_transfer(shard, target)
        end = time.time() + 30.0
        while cluster.leader(shard) != target:
            assert time.time() < end, "leadership did not move"
            time.sleep(0.05)
        cluster.traffic("b")
        # reads on every shard at once: some register (at a completion
        # in the room check) after the plan loop put their row on the
        # lane, about once a second here; with _retake_lane_rows taken
        # out the oracle reports each of them
        stop = time.time() + 2.0

        def reader(s):
            nh = cluster.nhs[cluster.leader(s)]
            while time.time() < stop:
                try:
                    nh.sync_read(s, "b0", timeout=2.0)
                except Exception:  # noqa: BLE001 - churn: clients retry
                    pass

        readers = [threading.Thread(target=reader, args=(s,))
                   for s in SHARDS]
        for t in readers:
            t.start()
        for t in readers:
            t.join(30.0)
        assert failures() == 0, hostplane.PARITY_FAILURES[:3]
    st1 = dict(core.stats)
    assert st1["launches"] > st0["launches"]
    assert st1["tick_lane_rows"] > st0["tick_lane_rows"]
    assert st1["device_reads"] > st0["device_reads"], (
        "no ReadIndex read went through the device: nothing tested")
    assert st1["divergence_halts"] == 0 and st1["pipeline_resets"] == 0


# -- (b) one hand-built generation, both ways ----------------------------
def _stub(shard, replica, ctx=None):
    node = types.SimpleNamespace(
        shard_id=shard, replica_id=replica, device_reads=ReadIndex())
    if ctx is not None:
        node.device_reads.add_request(5, ctx, 0)
    return node


def _hand_built():
    """batch (the active rows), the tick lane, and the whole stepped
    set as tuples (active first, the lane after: the oracle's input),
    as the plan loop would hand them over, rows out of order as a real
    launch has them."""
    batch, lane = [], hostplane.TickLane()

    def act(g, plan, ctx=None):
        batch.append((_stub(g, 1, ctx), g, None, plan))

    lane.add(_stub(9, 1), 9, 1, 1)
    lane.add(_stub(2, 1), 2, 3, 3)
    # the fast lane's sole tick, but a device read waits for its quorum:
    # the tick carries the ctx in its hint lanes
    act(4, [("tick", 2)], ctx=SystemCtx(low=7, high=9))
    # two of the three ticks drained were swallowed by quiesce, and the
    # backlog cap dropped four more: the device is fed 2 all the same
    lane.add(_stub(17, 1), 17, 2, 3, gc=4)
    ents = [Entry(term=3, index=0, cmd=b"p" * 16, key=11)]
    act(6, [("tick", 1), ("prop", ents)])
    # full path, and the plan came out as a lone tick all the same
    act(12, [("tick", 1)])
    lane.add(_stub(30, 1), 30, 1, 1)
    act(20, [("read", SystemCtx(low=3, high=4))])
    act(21, [("tick", 2), ("msg", Message(
        type=MessageType.PROPOSE, to=1, from_=2, shard_id=21,
        entries=(Entry(term=3, index=0, cmd=b"f" * 16, key=12),)))])
    act(25, [("msg", Message(
        type=MessageType.HEARTBEAT_RESP, to=1, from_=3, shard_id=25,
        term=3, log_index=4))])
    lane.add(_stub(0, 1), 0, 1, 1)
    return batch, lane, batch + colocated.ColocatedVectorEngine._lane_as_batch(
        lane)


@pytest.fixture
def core():
    group = ColocatedEngineGroup(**GEOM)
    group.factory(None)
    return group.core


def test_a_hand_built_generation_encodes_the_same_both_ways(core):
    batch, tick_lane, whole_batch = _hand_built()
    batch_gs = [g for _, g, _, _ in whole_batch]
    assert batch_gs == [4, 6, 12, 20, 21, 25, 9, 2, 17, 30, 0]
    tick_lane.seal()
    assert tick_lane.gs_np.tolist() == [9, 2, 17, 30, 0]
    assert tick_lane.fed_np.tolist() == [1, 3, 2, 1, 1]
    # both clocks advance by the ticks drained plus the ticks dropped
    assert tick_lane.clock_np.tolist() == [1, 3, 7, 1, 1]
    # a slow path gets one row's inputs back, and only then
    si = whole_batch[8][2]
    assert whole_batch[8][1] == 17 and (si.ticks, si.gc_ticks) == (3, 4)
    assert whole_batch[8][3] == [("tick", 2)]
    reads0 = core.stats["device_reads"]
    lane = core._encode_generation(
        batch, tick_lane.gs_np, tick_lane.fed_np)
    whole = core._encode_generation(whole_batch, [], [])
    assert core.stats["device_reads"] == reads0 + 2  # one `read`, twice
    gs = np.asarray(batch_gs, np.int64)
    hostplane.assert_encode_parity(whole_batch, gs, lane, whole, tick_lane)

    alive = core._lanes.alive_mask()
    alive[[0, 2, 4, 6, 9]] = True
    combos = [
        colocated._combo_np(e.tick_counts, alive, gs,
                            np.asarray(e.prop_rows, np.int64))
        for e in (lane, whole)
    ]
    assert combos[0].dtype == np.int32 and combos[0].shape == (32, 4)
    assert combos[0].tobytes() == combos[1].tobytes()
    ticks = combos[0][:, colocated._C_TICKS]
    # the lane's rows and the full-path lone tick are counts; the rows
    # with a hint, a proposal, a read or a message are not
    assert {g: int(ticks[g]) for g in np.nonzero(ticks)[0]} == {
        9: 1, 2: 3, 17: 2, 12: 1, 30: 1, 0: 1}
    assert [g for g, _ in lane.sparse] == [4, 6, 20, 21, 25]
    assert [g for g, _ in whole.sparse] == [4, 6, 20, 21, 25]
    for (_, got), (_, want) in zip(lane.sparse, whole.sparse):
        assert got == want
    tick4 = lane.sparse[0][1][0]
    assert (tick4.type, tick4.log_index, tick4.hint, tick4.hint_high) == (
        MessageType.LOCAL_TICK, 2, 7, 9)
    assert lane.staging == whole.staging and set(lane.staging) == {6, 21}
    assert lane.prop_rows == whole.prop_rows == [6, 21]
    # the active rows' counts in the encode, the lane's in its column
    assert lane.tick_fed == {4: 2, 6: 1, 12: 1, 21: 2}
    assert whole.tick_fed == {
        9: 1, 2: 3, 4: 2, 17: 2, 6: 1, 12: 1, 30: 1, 21: 2, 0: 1}
    assert combos[0][:, colocated._C_PROP].nonzero()[0].tolist() == [6, 21]
    assert sorted(combos[0][:, colocated._C_BATCH].nonzero()[0]) == sorted(
        batch_gs)


def test_the_oracle_names_a_row_the_lane_should_not_have_taken(core):
    batch, tick_lane, whole_batch = _hand_built()
    tick_lane.seal()
    gs = np.asarray([g for _, g, _, _ in whole_batch], np.int64)
    whole = core._encode_generation(whole_batch, [], [])
    # row 4 (a pending read ctx) left on the lane: its hint is lost, it
    # reads as a count and is no dense row
    wrong_lane = hostplane.TickLane()
    for node, g, fed, ticks in zip(tick_lane.nodes, tick_lane.gs,
                                   tick_lane.fed, tick_lane.ticks):
        wrong_lane.add(node, g, fed, ticks)
    wrong_lane.add(batch[0][0], 4, 2, 2)
    wrong_lane.seal()
    wrong = core._encode_generation(
        [r for r in batch if r[1] != 4], wrong_lane.gs_np,
        wrong_lane.fed_np)
    with pytest.raises(hostplane.HostPlaneParityError, match="tick_counts"):
        hostplane.assert_encode_parity(
            whole_batch, gs, wrong, whole, wrong_lane)
    with pytest.raises(hostplane.HostPlaneParityError, match="sparse rows"):
        hostplane.assert_encode_parity(
            whole_batch, gs,
            wrong._replace(tick_counts=whole.tick_counts), whole,
            wrong_lane)
    # a wrong count on the lane
    tick_lane.fed = [n + 1 for n in tick_lane.fed]
    tick_lane.seal()
    wrong = core._encode_generation(
        batch, tick_lane.gs_np, tick_lane.fed_np)
    with pytest.raises(hostplane.HostPlaneParityError, match="tick_counts"):
        hostplane.assert_encode_parity(
            whole_batch, gs, wrong, whole, tick_lane)
    with pytest.raises(hostplane.HostPlaneParityError, match="tick_fed"):
        hostplane.assert_encode_parity(
            whole_batch, gs,
            wrong._replace(tick_counts=whole.tick_counts), whole,
            tick_lane)
    # row ids out of order: the completion indexes the stepped set by them
    with pytest.raises(hostplane.HostPlaneParityError, match="batch_gs"):
        hostplane.assert_encode_parity(whole_batch, gs[::-1], whole, whole)
    before = hostplane.PARITY_FAILURE_COUNT
    hostplane.check_encode_parity(whole_batch, gs[::-1], whole, whole)
    assert hostplane.PARITY_FAILURE_COUNT == before + 1
    hostplane.PARITY_FAILURE_COUNT = before
    hostplane.PARITY_FAILURES.clear()


def test_a_read_registered_after_the_plan_loop_takes_its_row_off_the_lane(
        core):
    batch, tick_lane, whole_batch = _hand_built()
    by_g = {g: node for node, g, _, _ in whole_batch}
    late, gone, other = by_g[17], by_g[2], _stub(31, 1)
    for node, g, _si, _plan in whole_batch:
        core._row_of[(node.shard_id, node.replica_id)] = g
    try:
        # 17 registers a read; 2 registered one that was confirmed since;
        # 31 is no row of this launch; 4 was active already
        late.device_reads.add_request(8, SystemCtx(low=1, high=2), 0)
        core._read_ctx_new[:] = [late, gone, other, by_g[4]]
        core._retake_lane_rows(batch, tick_lane)
    finally:
        core._row_of.clear()
    assert core._read_ctx_new == []
    assert tick_lane.gs == [9, 2, 30, 0] and tick_lane.fed == [1, 3, 1, 1]
    assert tick_lane.ticks == [1, 3, 1, 1] and tick_lane.gc == {}
    assert len(tick_lane.nodes) == 4 and late not in tick_lane.nodes
    # the row comes back as a batch row has always been: its inputs
    # (the ticks drained and dropped) and the plan of its one tick
    node, g, si, plan = batch[-1]
    assert len(batch) == 7 and node is late and g == 17
    assert (si.ticks, si.gc_ticks) == (3, 4) and plan == [("tick", 2)]
    tick_lane.seal()
    lane = core._encode_generation(
        batch, tick_lane.gs_np, tick_lane.fed_np)
    whole_batch = batch + core._lane_as_batch(tick_lane)
    whole = core._encode_generation(whole_batch, [], [])
    gs = np.asarray([g for _, g, _, _ in whole_batch], np.int64)
    hostplane.assert_encode_parity(whole_batch, gs, lane, whole, tick_lane)
    assert 17 in dict(lane.sparse)


# -- (c) the counter -----------------------------------------------------
def test_tick_lane_rows_counts_the_rows_that_never_became_messages(cluster):
    assert cluster.fresh_engine["tick_lane_rows"] == 0
    core = cluster.core
    if hostplane.PARITY:
        pytest.skip("the oracle encodes every row a second time")
    per_row = [0]
    encode = core._encode_rows

    def counting(batch, slot_offset=0):
        per_row[0] += len(batch)
        return encode(batch, slot_offset)

    def snapshot():
        st = dict(core.stats)
        st[core._ph_key] += (time.perf_counter() - core._ph_t) * 1000.0
        return st

    with core._lock:
        core._encode_rows = counting
        st0 = snapshot()
    try:
        cluster.traffic("c", n=2)
        time.sleep(0.3)
    finally:
        with core._lock:
            del core._encode_rows
            st1 = snapshot()
    d = {k: st1[k] - st0[k] for k in st1
         if isinstance(st1[k], (int, float))}
    assert d["launches"] > 0 and d["tick_lane_rows"] > 0 and per_row[0] > 0
    assert d["tick_lane_rows"] + per_row[0] == d["device_rows_stepped"]
    assert d["device_rows_active"] <= per_row[0]
    # the phases still add up to the step calls' time (PR 26's account)
    named = sum(d[k] for k in PHASES)
    assert d["t_encode_ms"] > 0
    assert named + d["t_misc_ms"] == pytest.approx(d["t_launch_ms"],
                                                   rel=1e-6, abs=0.01)
