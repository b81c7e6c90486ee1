"""The host's account (PR 26): every millisecond of the colocated core's
wall time lands in one named counter, the WAL, the apply workers, the
gateway's queue and the lease gate count where the work happens, and the
launch phases are regions on the profiler's clock.

One cluster for the module: 8 shards x 3 replicas on three NodeHosts
sharing one ColocatedEngineGroup (the geometry ``chip_smoke.py --shards
8`` and the benchmark's rehearsals compile), tan WAL, a Gateway in front.
Counts and relations only: a CPU run tells no time that matters.
"""
import glob
import json
import os
import re
import shutil
import threading
import time

import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    Gateway,
    GatewayConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu import node as node_mod
from dragonboat_tpu.gateway.gateway import GatewayFuture
from dragonboat_tpu.ops import colocated, registry
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.storage.tan import tan_logdb_factory
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import KVStore, set_cmd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEOM = dict(capacity=32, P=3, W=16, M=8, E=4, O=32, budget=4)
SHARDS = list(range(1, 9))
ADDRS = {1: "acct-nh-1", 2: "acct-nh-2", 3: "acct-nh-3"}

# a step call's time, phase by phase: they add up to t_launch_ms
PHASES = (
    "t_coalesce_ms", "t_plan_ms", "t_upload_ms", "t_encode_ms",
    "t_dispatch_ms", "t_dev_blob_ms", "t_merge_ms", "t_detail_ms",
    "t_updates_ms", "t_persist_ms", "t_wake_ms",
)
ENGINE_KEYS = PHASES + (
    "t_launch_ms", "t_between_ms", "t_misc_ms", "t_lock_wait_ms",
    "t_wal_ms", "wal_appends", "wal_bytes", "wal_records",
    "device_rows_active", "apply_batches", "apply_entries", "t_apply_ms",
    "t_apply_wait_ms", "tick_lane_rows", "completion_rows_walked",
    # PR 32: leader transfers and what a change of leader costs proposals
    "leader_transfers_requested", "leader_transfers_done",
    "leader_transfers_aborted", "t_transfer_ms",
    "proposals_dropped_truncated", "device_transfers", "deferred_inputs",
)
GATEWAY_KEYS = (
    "proposed", "t_queue_wait_ms", "t_ack_lag_ms", "poll_checks",
    "poll_passes", "wakes", "wakes_timed", "t_worker_cpu_ms",
    "read_fallback_not_leader",
    "read_fallback_no_commit_in_term", "read_fallback_apply_lag",
    "read_fallback_lease_expiring", "reroutes",
)
REGIONS = tuple(
    "raft-colocated-" + p for p in (
        "coalesce", "plan", "upload", "encode", "step", "select",
        "readback", "merge", "detail", "updates", "persist", "wake",
        "lockwait",
    )
) + ("raft-apply", "gateway-poll")


class Cluster:
    def __init__(self, workdir):
        reset_inproc_network()
        self.group = ColocatedEngineGroup(**GEOM)
        self.nhs = {}
        for rid, addr in ADDRS.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=os.path.join(workdir, f"nh-{rid}"),
                rtt_millisecond=5,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=self.group.factory,
                    logdb_factory=tan_logdb_factory,
                ),
            ))
        self.core = self.group.core
        # nothing has stepped yet: what construction left in the table
        self.fresh_engine = dict(self.core.stats)
        self.gw = Gateway({ADDRS[r]: nh for r, nh in self.nhs.items()},
                          GatewayConfig(workers=2))
        self.fresh_gateway = self.gw.stats()
        for s in SHARDS:
            for rid, nh in self.nhs.items():
                nh.start_replica(ADDRS, False, KVStore, Config(
                    replica_id=rid, shard_id=s, election_rtt=20,
                    heartbeat_rtt=2, pre_vote=True, check_quorum=True))
        deadline = time.time() + 60.0
        first = self.nhs[1]
        while not all(first.get_leader_id(s)[1] for s in SHARDS):
            assert time.time() < deadline, "no leader on every shard in 60s"
            time.sleep(0.05)

    def write(self, shard, key, value, timeout=20.0):
        return self.gw.noop_handle(shard).sync_propose(
            set_cmd(key, value), timeout=timeout)

    def engine(self):
        """The core's stats at one instant, with the time the launch
        clock has not charged yet added to the phase it is running."""
        core = self.core
        with core._lock:
            st = dict(core.stats)
            st[core._ph_key] += (time.perf_counter() - core._ph_t) * 1000.0
            return st, time.perf_counter()

    def leader_node(self, shard):
        rid = self.nhs[1].get_leader_id(shard)[0]
        return self.nhs[rid]._nodes[shard]

    def close(self):
        self.gw.close()
        for nh in self.nhs.values():
            nh.close()


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("acct"))
    c = Cluster(workdir)
    yield c
    c.close()
    shutil.rmtree(workdir, ignore_errors=True)


def delta(after, before):
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and k in before}


# -- present and zero before anything ran ------------------------------
def test_every_new_key_is_there_and_zero_on_a_fresh_engine_and_gateway(
        cluster):
    for k in ENGINE_KEYS:
        assert cluster.fresh_engine[k] == 0, k
    # the three dispatch timers under device names became t_dispatch_ms
    for gone in [f"t_dev_{p}_ms" for p in ("step", "route", "sel")] + [
            "t_device_ms"]:
        assert gone not in cluster.core.stats
    for k in GATEWAY_KEYS:
        assert cluster.fresh_gateway[k] == 0, k


# -- the account closes -------------------------------------------------
def test_phases_add_up_to_the_launch_and_launch_plus_between_to_the_wall(
        cluster):
    st0, t0 = cluster.engine()
    for i in range(40):
        cluster.write(SHARDS[i % len(SHARDS)], f"acct{i}", b"v" * 16)
    st1, t1 = cluster.engine()
    d = delta(st1, st0)
    elapsed_ms = (t1 - t0) * 1000.0
    assert d["launches"] > 0
    named = sum(d[k] for k in PHASES)
    # one clock, one phase at a time: with the glue between the phases
    # (t_misc_ms) they come to the whole, and nothing is counted twice
    assert named + d["t_misc_ms"] == pytest.approx(d["t_launch_ms"],
                                                   rel=1e-6, abs=0.01)
    # the glue is ~0.4 ms a launch whatever the size: an eighth of a
    # 3 ms launch here, under a hundredth of one at 1,000 shards (where
    # PERF.md holds the named phases to 90 %)
    assert named >= 0.75 * d["t_launch_ms"], d
    assert d["t_launch_ms"] + d["t_between_ms"] == pytest.approx(
        elapsed_ms, rel=0.10)
    assert d["t_wal_ms"] <= d["t_persist_ms"]
    assert 0 < d["device_rows_active"] <= d["device_rows_stepped"]


def test_the_account_closes_with_the_lane_carried_through_the_completion(
        cluster):
    """PR 29: the tick lane's rows stay columns through the completion
    and the wake is one call a member NodeHost; the same clock still
    charges every millisecond once, and the completion counts the rows
    it walked in Python."""
    st0, _ = cluster.engine()
    time.sleep(0.3)  # launches that carry nothing but ticks
    for i in range(16):
        cluster.write(SHARDS[i % len(SHARDS)], f"lane{i}", b"v" * 16)
    st1, _ = cluster.engine()
    d = delta(st1, st0)
    assert d["launches"] > 0 and d["tick_lane_rows"] > 0
    named = sum(d[k] for k in PHASES)
    assert named + d["t_misc_ms"] == pytest.approx(d["t_launch_ms"],
                                                   rel=1e-6, abs=0.01)
    for k in ("t_plan_ms", "t_merge_ms", "t_detail_ms", "t_updates_ms",
              "t_wake_ms"):
        assert d[k] > 0, k
    # every write's rows are walked (a leader and two followers, over
    # the rounds of its wave), a row that only ticked is not: 24 rows
    # resident, so a launch that walked them all every round would
    # count three times its stepped rows
    assert 16 * 3 <= d["completion_rows_walked"]
    assert d["completion_rows_walked"] < d["device_rows_stepped"]


# -- the WAL and the apply workers --------------------------------------
def test_tan_lane_rows_persist_and_the_wal_counts_appends_and_bytes(
        cluster):
    payload = os.urandom(64)  # under tan's compression threshold
    n = 10
    st0, _ = cluster.engine()
    c0 = cluster.gw.stats()["committed"]
    for i in range(n):  # one at a time: no two writes share an append
        cluster.write(SHARDS[i % len(SHARDS)], f"wal{i}", payload)
    # the third replica's save and the followers' applies may trail the
    # acknowledgement; the fold lags by one step call
    deadline = time.time() + 10.0
    while True:
        d = delta(cluster.engine()[0], st0)
        if (d["wal_bytes"] >= n * len(payload) * 3
                and d["apply_entries"] >= n * 3) or time.time() > deadline:
            break
        time.sleep(0.05)
    assert cluster.gw.stats()["committed"] - c0 == n
    assert d["t_persist_ms"] > 0 and d["t_wal_ms"] > 0
    assert d["lane_rows"] > 0, "no lane row persisted: nothing tested"
    # a write is on a quorum's WAL before its ack: two appends at least
    assert d["wal_appends"] >= 2 * n, d
    assert d["wal_records"] >= d["wal_appends"]
    assert d["wal_bytes"] >= n * len(payload) * 3, d
    assert d["apply_entries"] >= n * 3, d
    assert 0 < d["apply_batches"] <= d["apply_entries"]
    assert d["t_apply_ms"] > 0 and d["t_apply_wait_ms"] >= 0


# -- the gateway's queue and the poll's lag -----------------------------
def test_gateway_counts_queue_wait_ack_lag_and_polls(cluster):
    g0 = cluster.gw.stats()
    n = 24
    handles = [cluster.gw.noop_handle(s) for s in SHARDS]
    futs = [handles[i % len(handles)].propose(set_cmd(f"gw{i}", b"x"), 20.0)
            for i in range(n)]
    for f in futs:
        f.result(20.0)
    g1 = cluster.gw.stats()
    d = delta(g1, g0)
    assert d["committed"] == n
    assert d["proposed"] >= d["committed"]
    assert d["t_queue_wait_ms"] >= 0 and d["t_ack_lag_ms"] >= 0
    assert d["poll_checks"] >= d["committed"]
    assert 0 < d["poll_passes"] <= d["poll_checks"]
    assert 0 <= d["wakes_timed"] < d["wakes"]
    assert d["t_worker_cpu_ms"] > 0.0
    for f in futs:
        assert f.t_done > 0.0


@pytest.mark.parametrize("when", ["before", "after"])
def test_future_callback_fires_exactly_once(cluster, when):
    calls = []
    fut = cluster.gw.noop_handle(1).propose(set_cmd("cb-" + when, b"1"), 20.0)
    if when == "after":
        fut.result(20.0)
        assert fut.t_done > 0.0
    fut.add_done_callback(calls.append)
    fut.result(20.0)
    deadline = time.time() + 5.0
    while not calls and time.time() < deadline:
        time.sleep(0.01)  # "before": it runs on the gateway's worker
    time.sleep(0.05)
    assert calls == [fut]
    assert 0.0 < fut.t_done <= time.monotonic()


def test_a_raising_callback_is_swallowed_and_the_next_one_still_runs():
    fut, calls = GatewayFuture(), []
    fut.add_done_callback(lambda f: 1 / 0)
    fut.add_done_callback(calls.append)
    fut._complete(result=7)
    assert calls == [fut] and fut.result(0) == 7
    fut.add_done_callback(lambda f: 1 / 0)  # already done: called at once


# -- why a read left the lease ------------------------------------------
def test_fallback_reasons_add_up_and_held_back_apply_counts_as_apply_lag(
        cluster):
    gw, shard = cluster.gw, 3
    cluster.write(shard, "lease-k", b"v0")
    gw.read(shard, "lease-k", timeout=10.0)  # the route is known from here
    reasons = [k for k in GATEWAY_KEYS if k.startswith("read_fallback_")]
    g0 = gw.stats()
    for _ in range(20):
        assert gw.read(shard, "lease-k", timeout=10.0) == b"v0"
    node = cluster.leader_node(shard)
    assert node.lease_probe(2)[0] in (node_mod.LEASE_HELD,
                                      node_mod.LEASE_MISS_EXPIRING)
    follower = next(nh._nodes[shard] for nh in cluster.nhs.values()
                    if nh._nodes[shard] is not node)
    assert follower.lease_probe(2) == (node_mod.LEASE_MISS_NOT_LEADER, 0)
    assert follower.lease_remaining_ticks() == 0
    assert not follower.lease_held()

    # hold the leader's apply back: commits run ahead of last_applied
    got = []
    with node._apply_lock:
        fut = gw.noop_handle(shard).propose(set_cmd("lease-k", b"v1"), 20.0)
        deadline = time.time() + 10.0
        while node.sm.last_applied >= node.peer.raft.log.committed:
            assert time.time() < deadline, "the write did not commit"
            time.sleep(0.01)
        assert node.lease_probe(2) == (node_mod.LEASE_MISS_APPLY_LAG, 0)
        reader = threading.Thread(
            target=lambda: got.append(gw.read(shard, "lease-k", timeout=20.0)))
        reader.start()
        while (gw.stats()["read_fallback_apply_lag"]
               == g0["read_fallback_apply_lag"]):
            assert time.time() < deadline, "no read counted as apply lag"
            time.sleep(0.01)
    reader.join(20.0)
    assert not reader.is_alive()
    fut.result(20.0)
    assert got == [b"v1"]  # the fallback waited for the apply: not stale
    d = delta(gw.stats(), g0)
    assert d["read_fallback_apply_lag"] >= 1
    assert sum(d[k] for k in reasons) == d["read_fallbacks"]
    assert d["lease_reads"] + d["read_fallbacks"] == 21


# -- the regions on the profiler's clock --------------------------------
def test_a_short_trace_shows_every_region_on_the_host_plane(
        cluster, tmp_path):
    from jax.profiler import ProfileData

    from dragonboat_tpu.profiling import trace

    stop = threading.Event()

    def load():
        i = 0
        while not stop.is_set():
            cluster.write(SHARDS[i % len(SHARDS)], f"tr{i}", b"t" * 16)
            i += 1

    writer = threading.Thread(target=load)
    with trace(str(tmp_path)):
        writer.start()
        time.sleep(0.5)
        stop.set()
        writer.join(30.0)
    assert not writer.is_alive()
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    seen = set()
    for plane in ProfileData.from_file(pb).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                seen.update(e.name for e in line.events)
    assert set(REGIONS) <= seen, sorted(set(REGIONS) - seen)


# -- the names the benchmark matches device programs by -----------------
def test_the_three_device_programs_keep_the_names_the_benchmark_reads():
    """``benchmark/layers/step_roofline_pct.sat.json`` finds its program
    by ``module`` in the trace's ``XLA Modules`` line, and the ledger's
    ``device_ops`` are keyed the same way: a rename would empty them in
    silence."""
    want = {"_assemble_and_step", "_route_step", "_select_and_blob"}
    lowered = {}
    for ep in registry.ENTRY_POINTS:
        short = ep.name.rpartition(".")[2]
        if ep.name.startswith("colocated.") and short in want:
            assert getattr(colocated, short) is ep.fn
            args, kw = ep.build()
            text = ep.fn.lower(*args, **kw).as_text()
            lowered[short] = re.search(r"module @(\S+)", text).group(1)
    assert lowered == {n: "jit_" + n for n in want}
    with open(os.path.join(REPO, "benchmark", "layers",
                           "step_roofline_pct.sat.json")) as f:
        assert json.load(f)["module"] in want
