"""BASELINE config 3 at a small size: five replicas a group, the on-disk
key-value state machine behind the served path's text commands, YCSB A.

Eight groups x five replicas on five NodeHosts sharing one
``ColocatedEngineGroup(capacity=64, P=5)``, a Gateway in front, a seeded
50/50 read/update schedule of 1 KB records chosen by YCSB's scrambled
zipfian over 64 records.  The plain reference is the benchmark's own
(``benchmark/harness/reference.py``: a replay of the run's operation log
into one register per key, importing nothing of the program):

(a) every read inside the run and every key read back after it is one
    the replay allows;
(b) all FIVE replicas' state machines hold that value;
(c) the same schedule on the scalar ``raft/`` engine ends at the same
    commit index and applied state for every group (ROADMAP Reach);
(d) after the NodeHosts close, the state machines opened again from
    their directories hold the same state and applied index.

Each client thread writes only records of its own residue class, so a
key's writes have one order on any engine and (c) can compare final
states; reads go to every record, so (a) sees reads race writes.
"""
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np
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
from dragonboat_tpu.bigstate.ondisk import (
    OnDiskKV,
    TextOnDiskKV,
    put_cmd,
    text_kv_factory,
)
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.statemachine import SMEntry
from dragonboat_tpu.storage.vfs import StrictMemFS
from dragonboat_tpu.transport.inproc import reset_inproc_network

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from harness import traffic  # noqa: E402
from harness.loadgen import OK, READ, WRITE  # noqa: E402
from harness.reference import PlainRegisters  # noqa: E402

GEOM = dict(capacity=64, P=5, W=16, M=8, E=4, O=32, budget=4)
REPLICAS = (1, 2, 3, 4, 5)
N_SHARDS = 8
RECORDS = 64
THREADS = 8
OPS_PER_THREAD = 40
SEED = 28
OP_TIMEOUT_S = 20.0
# the benchmark's tick: at 5 ms the CPU's launches outrun a 20-tick
# election window under eight clients and leaders churn (PERF.md section 7)
RTT_MS = 20


def _schedule():
    """Per thread: (is_read, record) pairs.  A write's record is moved
    into the thread's own residue class (RECORDS % THREADS == 0)."""
    recs = traffic.scrambled_zipfian(
        RECORDS, THREADS * OPS_PER_THREAD, SEED).reshape(
            THREADS, OPS_PER_THREAD)
    is_read = np.arange(THREADS * OPS_PER_THREAD) % 2 == 0
    np.random.default_rng(SEED).shuffle(is_read)
    is_read = is_read.reshape(THREADS, OPS_PER_THREAD)
    out = []
    for t in range(THREADS):
        ops = []
        for r, rd in zip(recs[t].tolist(), is_read[t].tolist()):
            ops.append((rd, r if rd else r - r % THREADS + t))
        out.append(ops)
    return out


class Served:
    """One run of the schedule on one engine, and what it left."""

    def __init__(self, engine: str, root: str):
        self.engine = engine
        self.sm_root = os.path.join(root, "sm")
        self.values = traffic.RecordValues(SEED)
        self.keys = traffic.ycsb_key_names(RECORDS)
        self.key_shard = [1 + traffic.fnv64(k.encode()) % N_SHARDS
                          for k in self.keys]
        reset_inproc_network()
        self.group = (ColocatedEngineGroup(**GEOM)
                      if engine == "colocated" else None)
        addrs = {r: f"ods-{engine}-{r}" for r in REPLICAS}
        self.nhs = {}
        for rid, addr in addrs.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=os.path.join(root, f"nh-{rid}"),
                rtt_millisecond=RTT_MS,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=(self.group.factory
                                         if self.group else None),
                ),
            ))
        self.gw = Gateway({addrs[r]: nh for r, nh in self.nhs.items()},
                          GatewayConfig(workers=2))
        sm = text_kv_factory(self.sm_root)
        for s in range(1, N_SHARDS + 1):
            for rid, nh in self.nhs.items():
                nh.start_replica(addrs, False, sm, Config(
                    replica_id=rid, shard_id=s, election_rtt=20,
                    heartbeat_rtt=2, pre_vote=True, check_quorum=True))
        deadline = time.monotonic() + 60.0
        while not all(self.nhs[1].get_leader_id(s)[1]
                      for s in range(1, N_SHARDS + 1)):
            assert time.monotonic() < deadline, "no leader everywhere"
            time.sleep(0.05)
        self.ops = []
        self.failed = []
        self._run()
        self._settle()

    def _run(self) -> None:
        logs = [[] for _ in range(THREADS)]

        def client(tid, todo):
            log = logs[tid]
            for n, (rd, r) in enumerate(todo):
                k, s = self.keys[r], self.key_shard[r]
                t_i = time.monotonic()
                try:
                    if rd:
                        got = self.gw.read(s, k, timeout=OP_TIMEOUT_S)
                        vid = -1
                    else:
                        vid = (tid << 32) | n
                        cmd = f"{k}={self.values.encode(vid)}".encode()
                        self.gw.noop_handle(s).propose(
                            cmd, timeout=OP_TIMEOUT_S).result(
                                OP_TIMEOUT_S + 1.0)
                        got = None
                except Exception as e:  # noqa: BLE001 — (c) needs every op
                    self.failed.append((tid, n, repr(e)))
                    return
                log.append([READ if rd else WRITE, s, k, vid, t_i, t_i,
                            time.monotonic(), OK, got])

        threads = [threading.Thread(target=client, args=(t, todo))
                   for t, todo in enumerate(_schedule())]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        for log in logs:
            self.ops.extend(log)
        self.regs = PlainRegisters(self.ops, self.values)
        self.writes_of = {}
        for op in self.ops:
            if op[0] == WRITE:
                self.writes_of[op[1]] = self.writes_of.get(op[1], 0) + 1

    def _node(self, rid, shard):
        return self.nhs[rid]._get_node(shard)

    def _settle(self) -> None:
        """After the run: the linearizable read-back, then every
        replica's applied index and state once they stand still."""
        self.read_back = {}
        for (s, k) in sorted(self.regs.keys()):
            t_i = time.monotonic()
            got = self.gw.read(s, k, timeout=OP_TIMEOUT_S)
            self.read_back[(s, k)] = (got, t_i, time.monotonic())
        deadline = time.monotonic() + 15.0
        while True:
            self.applied = {
                s: {rid: self._node(rid, s).sm.last_applied
                    for rid in REPLICAS}
                for s in range(1, N_SHARDS + 1)}
            if all(len(set(a.values())) == 1 for a in self.applied.values()):
                break
            assert time.monotonic() < deadline, self.applied
            time.sleep(0.05)
        sms = {(s, rid): self._node(rid, s).sm.managed.sm
               for s in range(1, N_SHARDS + 1) for rid in REPLICAS}
        self.state = {sr: dict(sm._data) for sr, sm in sms.items()}
        self.sm_applied = {sr: sm.applied for sr, sm in sms.items()}
        # entries that are no client's: the no-op of each leader's term
        self.noops = {}
        for s in range(1, N_SHARDS + 1):
            last = self.applied[s][1]
            ents = self.nhs[1].logdb.iterate_entries(s, 1, 1, last + 1, 1 << 40)
            assert len(ents) == last, (s, len(ents), last)
            self.noops[s] = sum(1 for e in ents if not e.cmd)
        self.stats = dict(self.group.core.stats) if self.group else {}
        self.leader_changes = sum(nh.leader_changes
                                  for nh in self.nhs.values())

    def close(self) -> None:
        self.gw.close()
        for nh in self.nhs.values():
            nh.close()


@pytest.fixture(scope="module")
def runs():
    root = tempfile.mkdtemp(prefix="ods-served-")
    out = {}
    try:
        for engine in ("colocated", "scalar"):
            out[engine] = Served(engine, os.path.join(root, engine))
            out[engine].close()
        yield out
    finally:
        shutil.rmtree(root, ignore_errors=True)


@pytest.mark.parametrize("engine", ["colocated", "scalar"])
def test_every_operation_of_the_schedule_was_answered(runs, engine):
    run = runs[engine]
    assert run.failed == []
    assert len(run.ops) == THREADS * OPS_PER_THREAD


def test_reads_inside_the_run_are_allowed_by_the_register_replay(runs):
    run = runs["colocated"]
    reads = [op for op in run.ops if op[0] == READ]
    assert len(reads) > OPS_PER_THREAD
    bad = [op[:7] for op in reads
           if not run.regs.allows(op[1], op[2], op[8], op[5], op[6])]
    assert bad == []
    # the replay is no rubber stamp: the value of another key is refused
    some = next(op for op in reads if op[8] is not None)
    other = next(k for k in run.keys if k != some[2])
    assert not run.regs.allows(some[1], other, some[8], some[5], some[6])


def test_every_key_read_back_afterwards_is_allowed(runs):
    run = runs["colocated"]
    assert len(run.read_back) >= RECORDS // 4
    bad = [(sk, got) for sk, (got, t_i, t_a) in run.read_back.items()
           if not run.regs.allows(sk[0], sk[1], got, t_i, t_a)]
    assert bad == []


def test_all_five_replicas_hold_the_value_read_back(runs):
    run = runs["colocated"]
    for (s, k), (got, _t_i, _t_a) in run.read_back.items():
        held = {run.state[(s, rid)].get(k.encode()) for rid in REPLICAS}
        assert held == {got.encode()}, (s, k)


def test_scalar_engine_ends_at_the_same_commit_index_and_state(runs):
    dev, ref = runs["colocated"], runs["scalar"]
    for s in range(1, N_SHARDS + 1):
        # every write of the schedule is one entry, once, on both
        for run in (dev, ref):
            assert (run.applied[s][1] - run.noops[s]
                    == run.writes_of.get(s, 0)), (run.engine, s)
        # a second election would add a no-op to one side only; none is
        # expected at this size, and then the indexes are the same
        if dev.noops[s] == ref.noops[s]:
            assert dev.applied[s] == ref.applied[s], s
        for rid in REPLICAS:
            assert dev.state[(s, rid)] == ref.state[(s, 1)], (s, rid)


@pytest.mark.parametrize("engine", ["colocated", "scalar"])
def test_reopened_from_its_directory_a_state_machine_is_where_it_was(
        runs, engine):
    run = runs[engine]
    for s in range(1, N_SHARDS + 1):
        for rid in REPLICAS:
            sm = text_kv_factory(run.sm_root)(s, rid)
            try:
                # the last CLIENT entry: no-ops never reach update()
                assert sm.open(None) == run.sm_applied[(s, rid)]
                assert sm.applied <= run.applied[s][rid]
                assert sm._data == run.state[(s, rid)], (s, rid)
                assert sm.stats["replayed"] == run.writes_of.get(s, 0)
            finally:
                sm.close()


def test_the_run_is_counted_where_the_work_happened(runs):
    st = runs["colocated"].stats
    n_writes = sum(runs["colocated"].writes_of.values())
    assert st["t_sm_update_ms"] > 0.0
    assert st["t_sm_update_ms"] <= st["t_apply_ms"]
    # five replicas append every write to their own logs: 8-octet index,
    # 8-octet frame header, the command as proposed
    assert st["sm_wal_appends"] == 5 * n_writes
    assert st["sm_wal_bytes"] > 5 * n_writes * 1000
    assert st["leader_changes"] == runs["colocated"].leader_changes
    assert st["pipeline_resets"] == 0 and st["divergence_halts"] == 0


# ---------------------------------------------------------------------
# the state machine alone
# ---------------------------------------------------------------------
def _entries(pairs, first=1):
    return [SMEntry(index=first + i, cmd=f"{k}={v}".encode())
            for i, (k, v) in enumerate(pairs)]


class TestTextOnDiskKV:
    def test_text_commands_and_lookups(self):
        sm = TextOnDiskKV(1, 1, base_dir="/t/1-1", fs=StrictMemFS())
        assert sm.open(None) == 0
        out = sm.update(_entries([("a", "1"), ("b", "x=y"), ("a", "2")]))
        assert [e.result.value for e in out] == [1, 1, 1]
        assert sm.lookup("a") == "2"
        assert sm.lookup("b") == "x=y"      # only the first = splits
        assert sm.lookup("nope") is None
        assert sm.lookup(("stats",))["applied"] == 3
        # a command with no '=' is refused, not applied, and still logged
        bad = sm.update([SMEntry(index=4, cmd=b"no-separator")])
        assert bad[0].result.value == 0 and sm.applied == 4
        sm.close()

    def test_reopen_reports_the_applied_index_and_replays_the_wal(self):
        fs = StrictMemFS()
        sm = TextOnDiskKV(1, 1, base_dir="/t/1-1", fs=fs)
        sm.open(None)
        sm.update(_entries([("k", "v1"), ("k", "v2"), ("j", "w")]))
        sm.sync()
        sm.close()
        again = TextOnDiskKV(1, 1, base_dir="/t/1-1", fs=fs)
        assert again.open(None) == 3
        assert again.lookup("k") == "v2" and again.lookup("j") == "w"
        assert again.stats["replayed"] == 3
        again.close()

    def test_checkpoint_fold_keeps_the_text_state(self):
        fs = StrictMemFS()
        sm = TextOnDiskKV(1, 1, base_dir="/t/c", fs=fs, compact_wal_bytes=64)
        sm.open(None)
        sm.update(_entries([(f"k{i}", "v" * 40) for i in range(8)]))
        assert sm.stats["checkpoints"] >= 1
        sm.close()
        again = TextOnDiskKV(1, 1, base_dir="/t/c", fs=fs)
        assert again.open(None) == 8
        assert again.lookup("k7") == "v" * 40
        again.close()

    def test_snapshot_streams_between_replicas(self):
        import io

        fs = StrictMemFS()
        src = TextOnDiskKV(2, 1, base_dir="/t/2-1", fs=fs)
        src.open(None)
        src.update(_entries([("a", "1"), ("b", "2")]))
        buf = io.BytesIO()
        src.save_snapshot(src.prepare_snapshot(), buf, threading.Event())
        dst = TextOnDiskKV(2, 2, base_dir="/t/2-2", fs=fs)
        dst.open(None)
        buf.seek(0)
        dst.recover_from_snapshot(buf, threading.Event())
        assert dst.applied == 2 and dst.lookup("b") == "2"
        src.close()
        dst.close()

    def test_wal_counts_are_what_update_appended(self):
        sm = TextOnDiskKV(1, 1, base_dir="/t/w", fs=StrictMemFS())
        sm.open(None)
        assert sm.wal_counts() == (0, 0)
        sm.update(_entries([("a", "1"), ("b", "2")]))
        # frame: 8 header + 8 index + the command
        assert sm.wal_counts() == (2, 2 * (8 + 8 + 3))
        sm.close()

    def test_the_factory_roots_every_replica_under_its_directory(
            self, tmp_path):
        make = text_kv_factory(str(tmp_path))
        a, b = make(7, 1), make(7, 2)
        assert type(a) is TextOnDiskKV
        assert a.dir == str(tmp_path / "7-1") and b.dir == str(tmp_path / "7-2")
        a.open(None)
        a.update(_entries([("k", "v")]))
        a.close()
        assert sorted(os.listdir(tmp_path)) == ["7-1"]
        assert sorted(os.listdir(tmp_path / "7-1")) == ["wal.log"]

    def test_the_struct_codec_tier_is_unchanged(self):
        sm = OnDiskKV(1, 1, base_dir="/t/o", fs=StrictMemFS())
        sm.open(None)
        sm.update([SMEntry(index=1, cmd=put_cmd(b"k", b"v"))])
        assert sm.lookup(b"k") == b"v"
        assert sm.wal_counts()[0] == 1
        sm.close()


# ---------------------------------------------------------------------
# the counters alone
# ---------------------------------------------------------------------
def test_leader_changes_counts_new_term_leader_pairs_after_the_first(
        tmp_path):
    reset_inproc_network()
    nh = NodeHost(NodeHostConfig(
        nodehost_dir=str(tmp_path / "nh"), rtt_millisecond=RTT_MS,
        raft_address="ods-lc-1"))
    try:
        nh._on_leader_updated(7, 1, 1, 0)      # no leader yet: nothing
        nh._on_leader_updated(7, 1, 1, 2)      # the first leader: nothing
        nh._on_leader_updated(7, 1, 1, 0)      # lost sight of it: nothing
        nh._on_leader_updated(7, 1, 1, 2)      # the same one again: nothing
        assert nh.leader_changes == 0
        nh._on_leader_updated(7, 1, 2, 0)      # a term with no leader yet
        nh._on_leader_updated(7, 1, 2, 3)      # elected: one
        nh._on_leader_updated(7, 1, 3, 3)      # the same replica, a new term
        nh._on_leader_updated(8, 1, 1, 1)      # another shard's first
        assert nh.leader_changes == 2
        with nh._nodes_lock:                   # a shard that is stopped
            nh._nodes[7] = type("N", (), {"stop": lambda self: None})()
        nh.stop_shard(7)                       # ... is forgotten:
        nh._on_leader_updated(7, 1, 9, 2)      # its next leader is a first
        assert nh.leader_changes == 2
    finally:
        nh.close()


def test_an_in_memory_state_machine_counts_update_time_and_no_log():
    from dragonboat_tpu.rsm.managed import wrap_state_machine
    from examples.kv_gateway import KV

    managed = wrap_state_machine(KV(1, 1))
    managed.batched_update([SMEntry(index=1, cmd=b"k=v")])
    assert managed.update_s > 0.0
    assert managed.wal_counts() == (0, 0)
    assert managed.lookup("k") == "v"
    disk = wrap_state_machine(
        TextOnDiskKV(1, 1, base_dir="/t/m", fs=StrictMemFS()))
    disk.open(None)
    disk.batched_update([SMEntry(index=1, cmd=b"k=v")])
    assert disk.update_s > 0.0 and disk.wal_counts() == (1, 8 + 8 + 3)
    disk.close()
