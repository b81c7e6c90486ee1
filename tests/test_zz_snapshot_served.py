"""Snapshots and log compaction on the served path, at a small size.

Four groups x three replicas on three NodeHosts sharing one
``ColocatedEngineGroup(capacity=16)``, tan WAL, a Gateway in front,
``snapshot_entries`` 10 / ``compaction_overhead`` 5 (the benchmark's
``ycsb-a-1k3-snap``).  The reference is a plain dict a key, filled by
the seeded writes as they are acknowledged.

(a) after N writes every replica's state machine equals the dict, every
    save asked for ended saved or skipped, once per ten entries applied,
    each replica's log is gone at or below its newest snapshot's index
    less five and reads back above it, and no row went to the host path
    for a save;
(b) a save runs on a snapshot worker and holds no step: a state machine
    whose ``save_snapshot`` blocks does not stop OTHER groups' writes;
(c) a follower stopped, left behind the compaction point and started
    again installs the streamed snapshot and converges;
(d) NodeHosts closed and opened again recover from each replica's newest
    snapshot plus its log's tail;
(e) a request that meets a save in flight is counted skipped, and told;
(f) a close during a save leaks no thread and leaves no half-written
    snapshot directory, and the restart reads what was acknowledged;
and, under the snapshot worker's feet: (g) the log reader's range under
concurrent appends, compactions and reads, (h) the tan WAL's snapshot
records and removals beside the step worker's saves, replayed.
"""
import os
import random
import shutil
import tempfile
import threading
import time

import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    Gateway,
    GatewayConfig,
    IStateMachine,
    NodeHost,
    NodeHostConfig,
    Result,
)
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.pb import Entry, Snapshot, State, Update
from dragonboat_tpu.raft.log import LogCompactedError, LogUnavailableError
from dragonboat_tpu.request import RequestError
from dragonboat_tpu.statemachine import SnapshotStopped
from dragonboat_tpu.storage.logdb import LogDBLogReader
from dragonboat_tpu.storage.tan import TanLogDB, tan_logdb_factory
from dragonboat_tpu.transport.inproc import reset_inproc_network

GEOM = dict(capacity=16, P=3, W=16, M=8, E=4, O=32, budget=4)
REPLICAS = (1, 2, 3)
SHARDS = (1, 2, 3, 4)
EVERY, OVERHEAD = 10, 5
RTT_MS = 20
OP_TIMEOUT_S = 20.0


class GatedKV(IStateMachine):
    """``examples.kv_gateway.KV`` whose save can be held: a save of a
    replica listed in ``GATES`` waits for its event (or for ``done``,
    then gives up), and every save names the thread it ran on."""

    GATES = {}        # (shard, replica) -> threading.Event
    ENTERED = {}      # (shard, replica) -> threading.Event, set inside
    SAVED_ON = []     # thread names

    def __init__(self, shard_id, replica_id):
        self.key = (shard_id, replica_id)
        self.d = {}

    def update(self, entry):
        k, v = entry.cmd.decode().split("=", 1)
        self.d[k] = v
        return Result(value=len(self.d))

    def lookup(self, q):
        return self.d.get(q)

    def save_snapshot(self, w, files, done):
        GatedKV.SAVED_ON.append(threading.current_thread().name)
        gate = GatedKV.GATES.get(self.key)
        if gate is not None:
            GatedKV.ENTERED[self.key].set()
            while not gate.wait(0.01):
                if done.is_set():
                    raise SnapshotStopped()
        w.write(repr(sorted(self.d.items())).encode())

    def recover_from_snapshot(self, r, files, done):
        self.d = dict(eval(r.read(-1).decode()))  # noqa: S307 — our own repr


@pytest.fixture(autouse=True)
def _no_gates():
    GatedKV.GATES.clear()
    GatedKV.ENTERED.clear()
    del GatedKV.SAVED_ON[:]
    yield
    for gate in GatedKV.GATES.values():
        gate.set()


def _raft_threads():
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("tpu-raft-") and t.is_alive())


class Cluster:
    def __init__(self, root: str, tag: str):
        self.root = root
        self.expect = {}          # key -> value acknowledged
        self.closed = False
        reset_inproc_network()
        self.group = ColocatedEngineGroup(**GEOM)
        self.addrs = {r: f"snap-{tag}-{r}" for r in REPLICAS}
        self.nhs = {}
        for rid, addr in self.addrs.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=os.path.join(root, f"nh-{rid}"),
                rtt_millisecond=RTT_MS,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2,
                                        snapshot_shards=4),
                    step_engine_factory=self.group.factory,
                    logdb_factory=tan_logdb_factory,
                ),
            ))
        self.gw = Gateway({self.addrs[r]: nh for r, nh in self.nhs.items()},
                          GatewayConfig(workers=2))
        for s in SHARDS:
            for rid in REPLICAS:
                self.start(rid, s)
        self.wait_leaders()

    def start(self, rid: int, shard: int) -> None:
        self.nhs[rid].start_replica(self.addrs, False, GatedKV, Config(
            replica_id=rid, shard_id=shard, election_rtt=20,
            heartbeat_rtt=2, pre_vote=True, check_quorum=True,
            snapshot_entries=EVERY, compaction_overhead=OVERHEAD))

    def wait_leaders(self, shards=SHARDS) -> None:
        deadline = time.monotonic() + 60.0
        while not all(self.leader(s) for s in shards):
            assert time.monotonic() < deadline, "no leader everywhere"
            time.sleep(0.05)

    def leader(self, shard: int) -> int:
        for nh in self.nhs.values():
            try:
                lid, ok = nh.get_leader_id(shard)[:2]
            except RequestError:
                continue
            if ok:
                return lid
        return 0

    def node(self, rid: int, shard: int):
        return self.nhs[rid]._get_node(shard)

    def write(self, shard: int, key: str, value: str) -> None:
        self.gw.noop_handle(shard).propose(
            f"{key}={value}".encode(), timeout=OP_TIMEOUT_S,
        ).result(OP_TIMEOUT_S + 1.0)
        self.expect[key] = (shard, value)

    def write_many(self, n: int, seed: int, shards=SHARDS) -> None:
        rng = random.Random(seed)
        for i in range(n):
            s = rng.choice(shards)
            self.write(s, f"s{s}k{rng.randrange(12)}", f"v{seed}.{i}")

    def totals(self, rid: int) -> dict:
        return self.nhs[rid].host_totals.snapshot()

    def wait_saves_ended(self, rids=REPLICAS) -> None:
        deadline = time.monotonic() + 30.0
        while True:
            open_ = {
                rid: t for rid in rids
                if (t := self.totals(rid))["snapshots_requested"]
                != t["snapshots_saved"] + t["snapshots_skipped"]
                + t["snapshot_failures"]}
            if not open_:
                return
            assert time.monotonic() < deadline, open_
            time.sleep(0.02)

    def wait_converged(self, shards=SHARDS, rids=REPLICAS) -> None:
        """Every replica's state machine holds the dict."""
        deadline = time.monotonic() + 30.0
        while True:
            bad = [(k, rid, got) for k, (s, v) in self.expect.items()
                   if s in shards for rid in rids
                   if (got := self.nhs[rid].stale_read(s, k)) != v]
            if not bad:
                return
            assert time.monotonic() < deadline, bad[:5]
            time.sleep(0.05)

    def close(self) -> None:
        if not self.closed:
            self.closed = True
            self.gw.close()
            for nh in self.nhs.values():
                nh.close()


@pytest.fixture
def root():
    path = tempfile.mkdtemp(prefix="snap-served-")
    yield path
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture
def cluster(root, request):
    c = Cluster(root, request.node.name.replace("[", "-").replace("]", ""))
    yield c
    for gate in GatedKV.GATES.values():
        gate.set()
    c.close()


# -- (a) ---------------------------------------------------------------
@pytest.mark.parametrize("n_writes,seed", [(60, 35), (140, 36)])
def test_saves_compact_every_replica_and_no_row_leaves_the_device(
        cluster, n_writes, seed):
    c = cluster
    c.write_many(8, seed - 1)       # leaders settled, rows resident
    stats = c.group.core.stats
    host0 = stats["host_rows_stepped"]
    evicted0 = stats["snapshot_rows_evicted"]
    c.write_many(n_writes, seed)
    c.wait_converged()
    c.wait_saves_ended()
    assert stats["host_rows_stepped"] == host0
    assert stats["snapshot_rows_evicted"] == evicted0
    compacted = 0
    for rid in REPLICAS:
        t = c.totals(rid)
        assert t["snapshot_failures"] == 0
        assert t["snapshots_recovered"] == 0   # nobody fell behind
        applied = [c.node(rid, s).sm.last_applied for s in SHARDS]
        assert (t["snapshots_saved"] + t["snapshots_skipped"]
                == sum(a // EVERY for a in applied)), (rid, t, applied)
        assert t["snapshots_saved"] >= n_writes // (2 * EVERY)
        assert t["snapshot_bytes"] > 0
        compacted += t["log_entries_compacted"]
        db = c.nhs[rid].logdb
        for s, last in zip(SHARDS, applied):
            ss = db.get_snapshot(s, rid)
            if ss.is_empty():
                continue
            assert ss.index <= last
            gone_to = ss.index - OVERHEAD
            assert gone_to > 0
            assert db.iterate_entries(s, rid, 1, gone_to + 1, 1 << 40) == []
            assert db.term(s, rid, gone_to) is None
            kept = db.iterate_entries(s, rid, gone_to + 1, last + 1, 1 << 40)
            assert [e.index for e in kept] == list(
                range(gone_to + 1, last + 1))
            reader = c.node(rid, s).log_reader
            assert reader.log_range() == (gone_to + 1, last)
            assert reader.snapshot().index == ss.index
            with pytest.raises(LogCompactedError):
                reader.entries(gone_to, gone_to + 2, 1 << 40)
    assert compacted > 0
    # the engine's stats carry the same sums, folded once a step call
    deadline = time.monotonic() + 10.0
    want = sum(c.totals(rid)["snapshots_saved"] for rid in REPLICAS)
    while stats["snapshots_saved"] != want:
        assert time.monotonic() < deadline, (stats["snapshots_saved"], want)
        time.sleep(0.05)
    assert stats["t_snapshot_save_ms"] > 0.0
    assert stats["snapshot_failures"] == 0


# -- (b) and (e) -------------------------------------------------------
def _hold_saves_of(c: Cluster, shard: int, tag: str) -> None:
    """Shut the gates of ``shard``'s replicas and write to it until all
    three sit inside ``save_snapshot``.  A regular state machine saves
    under the lock its updates take, so the group applies nothing more
    until the gates open: the write whose apply asks for the save is the
    last that is answered."""
    for rid in REPLICAS:
        GatedKV.GATES[(shard, rid)] = threading.Event()
        GatedKV.ENTERED[(shard, rid)] = threading.Event()
    entered = [GatedKV.ENTERED[(shard, rid)] for rid in REPLICAS]
    for i in range(2 * EVERY):
        c.write(shard, f"s{shard}k{i % 12}", f"{tag}{i}")
        time.sleep(0.05)
        if any(e.is_set() for e in entered) or any(
                c.node(rid, shard).sm.last_applied % EVERY == 0
                for rid in REPLICAS):
            break
    for rid, e in zip(REPLICAS, entered):
        assert e.wait(20.0), rid


def test_a_blocked_save_holds_no_step_and_runs_on_a_snapshot_worker(cluster):
    c = cluster
    _hold_saves_of(c, 1, "held")
    # all three replicas of group 1 sit inside save_snapshot: the launch
    # loop of the whole cluster must still turn, for the other groups
    t0 = time.monotonic()
    c.write_many(45, 7, shards=(2, 3, 4))
    took = time.monotonic() - t0
    c.wait_converged(shards=(2, 3, 4))
    assert all(GatedKV.ENTERED[(1, rid)].is_set() for rid in REPLICAS)
    assert not any(g.is_set() for g in GatedKV.GATES.values())
    assert took < 60.0
    assert GatedKV.SAVED_ON, "no save ran"
    assert all(name.startswith("tpu-raft-snapsave-")
               for name in GatedKV.SAVED_ON), set(GatedKV.SAVED_ON)
    # the other groups' own saves ended meanwhile, on the same workers
    assert sum(c.totals(rid)["snapshots_saved"] for rid in REPLICAS) >= 3
    for gate in GatedKV.GATES.values():
        gate.set()
    c.wait_saves_ended()
    c.wait_converged()


def test_a_request_that_meets_a_save_in_flight_is_counted_skipped(cluster):
    c = cluster
    _hold_saves_of(c, 2, "held")
    before = {rid: c.totals(rid) for rid in REPLICAS}
    for rid in REPLICAS:
        with pytest.raises(RequestError):
            c.nhs[rid].sync_request_snapshot(2, timeout=2.0)
    for rid in REPLICAS:
        t = c.totals(rid)
        assert t["snapshots_skipped"] == before[rid]["snapshots_skipped"] + 1
        assert (t["snapshots_requested"]
                == before[rid]["snapshots_requested"] + 1)
    for gate in GatedKV.GATES.values():
        gate.set()
    c.wait_saves_ended()
    # and one that meets none is carried out, and answers with its index
    c.write(2, "s2k0", "after")
    c.wait_converged(shards=(2,))
    lead = c.leader(2)
    index = c.nhs[lead].sync_request_snapshot(2, timeout=10.0)
    assert index == c.node(lead, 2).sm.last_applied
    assert c.nhs[lead].logdb.get_snapshot(2, lead).index == index


# -- (c) ---------------------------------------------------------------
def test_a_follower_left_behind_the_compaction_point_installs_a_stream(
        cluster):
    c = cluster
    c.write_many(6, 11)
    lead = c.leader(3)
    lagger = next(r for r in REPLICAS if r != lead)
    c.nhs[lagger].stop_shard(3)
    before = {rid: c.totals(rid) for rid in REPLICAS}
    for i in range(3 * EVERY):
        c.write(3, f"s3k{i % 12}", f"behind{i}")
    c.wait_saves_ended([r for r in REPLICAS if r != lagger])
    lead = c.leader(3)
    assert lead and lead != lagger
    first = c.node(lead, 3).log_reader.log_range()[0]
    assert first > 2 * EVERY - OVERHEAD      # the lagger's next is gone
    c.start(lagger, 3)
    c.wait_converged(shards=(3,))
    streamed = sum(c.totals(r)["snapshots_streamed"]
                   - before[r]["snapshots_streamed"] for r in REPLICAS)
    assert streamed >= 1
    assert (c.totals(lagger)["snapshots_recovered"]
            >= before[lagger]["snapshots_recovered"] + 1)
    assert c.node(lagger, 3).log_reader.snapshot().index >= first - 1
    # the group still takes writes with all three, and the lagger's own
    # saves go on from the installed index
    for i in range(EVERY + 2):
        c.write(3, f"s3k{i}", f"after{i}")
    c.wait_converged()
    c.wait_saves_ended()
    assert all(c.totals(r)["snapshot_failures"] == 0 for r in REPLICAS)
    assert c.group.core.stats["divergence_halts"] == 0


# -- (d) ---------------------------------------------------------------
def test_nodehosts_opened_again_recover_from_snapshot_and_tail(root):
    c = Cluster(root, "d1")
    try:
        c.write_many(90, 21)
        c.wait_converged()
        c.wait_saves_ended()
        expect = dict(c.expect)
        applied = {(rid, s): c.node(rid, s).sm.last_applied
                   for rid in REPLICAS for s in SHARDS}
        snaps = {(rid, s): c.nhs[rid].logdb.get_snapshot(s, rid).index
                 for rid in REPLICAS for s in SHARDS}
    finally:
        c.close()
    assert _raft_threads() == []
    assert sum(1 for v in snaps.values() if v) >= 6
    c = Cluster(root, "d2")
    try:
        c.expect = expect
        for (rid, s), index in snaps.items():
            node = c.node(rid, s)
            # booted from the snapshot: the log below it is not there to
            # replay, the tail above it is
            if index:
                assert node.log_reader.log_range()[0] > index - OVERHEAD
                assert node.log_reader.snapshot().index == index
            assert node.sm.last_applied >= index
        for rid in REPLICAS:
            assert c.totals(rid)["snapshots_recovered"] == sum(
                1 for s in SHARDS if snaps[(rid, s)])
        c.wait_converged()
        for (rid, s), a in applied.items():
            assert c.node(rid, s).sm.last_applied >= a
        c.write_many(25, 22)
        c.wait_converged()
    finally:
        c.close()


# -- (f) ---------------------------------------------------------------
def test_a_close_during_a_save_leaks_nothing_and_restarts_clean(root):
    c = Cluster(root, "f1")
    try:
        c.write_many(20, 31, shards=(1, 2, 3))
        _hold_saves_of(c, 4, "held")
        expect = dict(c.expect)
        t0 = time.monotonic()
    finally:
        c.close()        # the gates stay shut: the saves must give up
    assert time.monotonic() - t0 < 25.0
    assert _raft_threads() == []
    assert not any(g.is_set() for g in GatedKV.GATES.values())
    for rid in REPLICAS:
        snapdir = os.path.join(root, f"nh-{rid}", "snapshots")
        names = os.listdir(snapdir)
        assert [n for n in names if n.endswith(".generating")] == []
        # group 4 never finished a save: nothing of it for a restart
        assert [n for n in names if n.startswith("snapshot-4-")] == []
        t = c.totals(rid)
        assert t["snapshot_failures"] == 0
        assert (t["snapshots_requested"] == t["snapshots_saved"]
                + t["snapshots_skipped"])
    GatedKV.GATES.clear()
    c = Cluster(root, "f2")
    try:
        c.expect = expect
        c.wait_converged()
    finally:
        c.close()


# -- (g) ---------------------------------------------------------------
def _entry(i: int, term: int = 1) -> Entry:
    return Entry(term=term, index=i, cmd=b"x")


@pytest.mark.parametrize("seed", [1, 2])
def test_log_reader_range_under_appends_compactions_and_reads(root, seed):
    """What the step worker (append, read) and a snapshot worker
    (create_snapshot, compact, remove) do to one reader at once: a read
    returns what was asked for or raises one of the two range errors,
    never a wrong term, a gap or a short list that starts late."""
    db = TanLogDB(os.path.join(root, f"tan-{seed}"), use_native=False)
    reader = LogDBLogReader(1, 1, db)
    n, stop, bad = 1500, threading.Event(), []

    def append():
        for i in range(1, n + 1):
            u = Update(shard_id=1, replica_id=1,
                       state=State(term=1 + i // 400, vote=1, commit=i - 1),
                       entries_to_save=[_entry(i, 1 + i // 400)])
            db.save_raft_state([u], 0)
            reader.append(u.entries_to_save)
        stop.set()

    def compact():
        rng = random.Random(seed)
        while not stop.is_set():
            first, last = reader.log_range()
            if last - first < 8:
                continue
            to = rng.randrange(first, last - 4)
            ss = Snapshot(index=to + 2, term=1 + (to + 2) // 400,
                          shard_id=1, replica_id=1, filepath="x")
            db.save_snapshots([Update(shard_id=1, replica_id=1, snapshot=ss)])
            reader.create_snapshot(ss)
            assert reader.compact(to) == to + 1 - first
            db.remove_entries_to(1, 1, to)

    def read():
        rng = random.Random(seed + 100)
        while not stop.is_set():
            first, last = reader.log_range()
            if last < first:
                continue
            i = rng.randrange(max(1, first - 3), last + 2)
            try:
                t = reader.term(i)
                if t != 1 + i // 400:
                    bad.append(("term", i, t))
                got = reader.entries(i, min(i + 4, last + 1), 1 << 40)
                if got and [e.index for e in got] != list(
                        range(i, i + len(got))):
                    bad.append(("entries", i, [e.index for e in got]))
            except (LogCompactedError, LogUnavailableError):
                pass
            except Exception as e:  # noqa: BLE001 — the finding
                bad.append(("raised", i, repr(e)))

    threads = [threading.Thread(target=f) for f in (append, compact, read)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    db.close()
    assert bad == []
    first, last = reader.log_range()
    assert last == n and 1 < first <= n
    assert reader.term(first - 1) == 1 + (first - 1) // 400


# -- (h) ---------------------------------------------------------------
def test_tan_snapshot_records_and_removals_beside_saves_replay(root):
    """Snapshot records and removals written by other threads than the
    saver's land in the WAL in some order with its batches; a replay of
    the segments ends at the state the mirror held."""
    path = os.path.join(root, "tan-h")
    db = TanLogDB(path)
    n = 600

    def save(shard):
        for i in range(1, n + 1):
            db.save_raft_state([Update(
                shard_id=shard, replica_id=1,
                state=State(term=1, vote=1, commit=i),
                entries_to_save=[_entry(i)])], 0)

    def snap(shard):
        done = 0
        while done + EVERY + OVERHEAD < n:
            have = db.read_raft_state(shard, 1, 0)
            last = have.first_index + have.entry_count - 1 if have else 0
            if last < done + EVERY:
                time.sleep(0.0005)
                continue
            done = last
            db.save_snapshots([Update(shard_id=shard, replica_id=1,
                                      snapshot=Snapshot(
                                          index=done, term=1, shard_id=shard,
                                          replica_id=1, filepath="x"))])
            db.remove_entries_to(shard, 1, done - OVERHEAD)

    threads = [threading.Thread(target=f, args=(s,))
               for s in (1, 2) for f in (save, snap)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    held = {}
    for s in (1, 2):
        ss = db.get_snapshot(s, 1)
        assert ss.index >= n - 2 * EVERY - OVERHEAD
        rs = db.read_raft_state(s, 1, 0)
        held[s] = (ss.index, rs.first_index, rs.entry_count, rs.state)
        assert rs.first_index + rs.entry_count - 1 == n
        assert db.term(s, 1, ss.index - OVERHEAD) is None
    db.close()
    again = TanLogDB(path)
    for s in (1, 2):
        ss = again.get_snapshot(s, 1)
        rs = again.read_raft_state(s, 1, 0)
        assert (ss.index, rs.first_index, rs.entry_count, rs.state) == held[s]
    again.close()
