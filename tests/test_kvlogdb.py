"""Sharded-KV LogDB: the classic key-encoded backend (SURVEY L4.2).

reference: internal/logdb (pebble ShardedDB) — key-encoded records,
one fsynced batch per save, batched/plain entry codecs, read cache [U].
Covers: the KV store's journal/checkpoint crash discipline, both entry
codecs through the ILogDB contract, the shared power-loss fuzz, and a
live NodeHost cluster on the backend.
"""
from __future__ import annotations

import random
import shutil

import pytest

from test_nodehost import nh_dir

from dragonboat_tpu.pb import Bootstrap, Snapshot, State, Update
from dragonboat_tpu.storage.kvlogdb import ShardedKVLogDB, kv_logdb_factory
from dragonboat_tpu.storage.kvstore import KVStore, WriteBatch
from dragonboat_tpu.storage.vfs import StrictMemFS
from test_vfs_crash import Model, ent, run_powerloss_fuzz, up


# ---------------------------------------------------------------------------
# KVStore
# ---------------------------------------------------------------------------
class TestKVStore:
    def test_roundtrip_and_order(self):
        fs = StrictMemFS()
        kv = KVStore("/kv", fs=fs)
        wb = WriteBatch()
        for k in (b"b", b"a", b"c", b"aa"):
            wb.put(k, b"v-" + k)
        kv.commit(wb)
        assert kv.get(b"aa") == b"v-aa"
        assert [k for k, _ in kv.iterate(b"a", b"c")] == [b"a", b"aa", b"b"]
        kv.close()
        kv2 = KVStore("/kv", fs=fs)  # replay
        assert [k for k, _ in kv2.iterate(b"", b"zz")] == [b"a", b"aa", b"b", b"c"]
        kv2.close()

    def test_delete_range_and_replay(self):
        fs = StrictMemFS()
        kv = KVStore("/kv", fs=fs)
        wb = WriteBatch()
        for i in range(10):
            wb.put(b"k%02d" % i, b"x")
        kv.commit(wb)
        wb = WriteBatch()
        wb.delete_range(b"k02", b"k07")
        wb.delete(b"k09")
        kv.commit(wb)
        want = [b"k00", b"k01", b"k07", b"k08"]
        assert [k for k, _ in kv.iterate(b"", b"zz")] == want
        kv.close()
        kv2 = KVStore("/kv", fs=fs)
        assert [k for k, _ in kv2.iterate(b"", b"zz")] == want
        kv2.close()

    def test_rotation_checkpoint_gc(self):
        fs = StrictMemFS()
        kv = KVStore("/kv", fs=fs, max_journal_bytes=400, gc_segments=1)
        for i in range(60):
            wb = WriteBatch()
            wb.put(b"key-%03d" % i, bytes(20))
            kv.commit(wb)
        assert len(kv._segments()) <= 4  # GC ran
        kv.close()
        kv2 = KVStore("/kv", fs=fs)
        assert len(kv2.iterate(b"", b"\xff")) == 60
        kv2.close()

    def test_torn_checkpoint_discarded(self):
        """A checkpoint without its END marker must be ignored wholesale
        — the pre-checkpoint segments still hold the data."""
        fs = StrictMemFS()
        kv = KVStore("/kv", fs=fs, max_journal_bytes=300, gc_segments=1)
        wrote = 0
        state = {"armed": False}

        def hook(op, path):
            # kill the first unlink: the checkpoint is written+synced but
            # old segments survive; then TEAR the checkpoint's tail
            if state["armed"] and op == "unlink":
                raise RuntimeError("boom")

        for i in range(40):
            wb = WriteBatch()
            wb.put(b"key-%03d" % i, bytes(20))
            state["armed"] = True
            fs.fault_hook = hook
            try:
                kv.commit(wb)
                wrote += 1
            except RuntimeError:
                wrote += 1  # the batch itself was durable pre-checkpoint
                break
            finally:
                fs.fault_hook = None
                state["armed"] = False
        fs.fault_hook = None
        # tear the active tail mid-checkpoint: keep only half the
        # unsynced bytes... (crash does that randomly; force via crash)
        fs.crash(random.Random(7))
        kv2 = KVStore("/kv", fs=fs)
        assert len(kv2.iterate(b"", b"\xff")) == wrote
        kv2.close()


# ---------------------------------------------------------------------------
# ILogDB contract, both codecs
# ---------------------------------------------------------------------------
@pytest.fixture(params=["batched", "plain"])
def kvdb(request):
    fs = StrictMemFS()

    def reopen():
        return ShardedKVLogDB(
            "/ldb", fs=fs, stores=2, batched=request.param == "batched",
            batch_size=4, max_journal_bytes=2000, gc_segments=2,
        )

    return fs, reopen


class TestShardedKVLogDB:
    def test_state_entries_roundtrip(self, kvdb):
        fs, reopen = kvdb
        db = reopen()
        db.save_bootstrap_info(1, 1, Bootstrap(addresses={1: "a1"}))
        db.save_raft_state(
            [up(1, 1, 2, [ent(i, 2, b"x%d" % i) for i in range(1, 11)], commit=3)],
            0,
        )
        rs = db.read_raft_state(1, 1, 0)
        assert rs.state == State(term=2, vote=0, commit=3)
        assert rs.first_index == 1 and rs.entry_count == 10
        ents = db.iterate_entries(1, 1, 3, 8, 1 << 30)
        assert [e.index for e in ents] == [3, 4, 5, 6, 7]
        assert ents[0].cmd == b"x3"
        assert db.term(1, 1, 10) == 2
        assert db.term(1, 1, 11) is None
        db.close()
        db2 = reopen()  # replay
        assert db2.read_raft_state(1, 1, 0).entry_count == 10
        assert db2.get_bootstrap_info(1, 1).addresses == {1: "a1"}
        assert [n.shard_id for n in db2.list_node_info()] == [1]
        db2.close()

    def test_conflicting_suffix_overwrite(self, kvdb):
        fs, reopen = kvdb
        db = reopen()
        db.save_raft_state(
            [up(1, 1, 1, [ent(i, 1) for i in range(1, 10)])], 0
        )
        # term-2 rewrite from index 6 truncates the old tail
        db.save_raft_state(
            [up(1, 1, 2, [ent(6, 2, b"n6"), ent(7, 2, b"n7")])], 0
        )
        ents = db.iterate_entries(1, 1, 1, 100, 1 << 30)
        assert [(e.index, e.term) for e in ents] == [
            (1, 1), (2, 1), (3, 1), (4, 1), (5, 1), (6, 2), (7, 2)
        ]
        db.close()
        db2 = reopen()
        assert db2.term(1, 1, 6) == 2 and db2.term(1, 1, 8) is None
        db2.close()

    def test_compaction_straddles_batches(self, kvdb):
        fs, reopen = kvdb
        db = reopen()
        db.save_raft_state(
            [up(1, 1, 1, [ent(i, 1) for i in range(1, 12)])], 0
        )
        db.remove_entries_to(1, 1, 6)  # mid-batch for batch_size=4
        assert db.iterate_entries(1, 1, 7, 100, 1 << 30)[0].index == 7
        assert db.term(1, 1, 6) is None
        rs = db.read_raft_state(1, 1, 0)
        assert rs.first_index == 7 and rs.entry_count == 5
        db.close()
        db2 = reopen()
        rs = db2.read_raft_state(1, 1, 0)
        assert rs.first_index == 7 and rs.entry_count == 5
        db2.close()

    def test_snapshot_and_import(self, kvdb):
        fs, reopen = kvdb
        db = reopen()
        db.save_raft_state([up(1, 1, 1, [ent(1, 1), ent(2, 1)])], 0)
        db.save_snapshots(
            [up(1, 1, 1, snapshot=Snapshot(index=2, term=1, shard_id=1))]
        )
        assert db.get_snapshot(1, 1).index == 2
        # stale snapshot ignored
        db.save_snapshots(
            [up(1, 1, 1, snapshot=Snapshot(index=1, term=1, shard_id=1))]
        )
        assert db.get_snapshot(1, 1).index == 2
        db.import_snapshot(Snapshot(index=9, term=3, shard_id=7), 2)
        rs = db.read_raft_state(7, 2, 0)
        assert rs.state.term == 3 and rs.state.commit == 9
        assert rs.first_index == 10 and rs.entry_count == 0
        db.close()
        db2 = reopen()
        assert db2.get_snapshot(7, 2).index == 9
        db2.close()

    def test_remove_node_data(self, kvdb):
        fs, reopen = kvdb
        db = reopen()
        db.save_raft_state([up(3, 2, 1, [ent(1, 1)])], 0)
        db.remove_node_data(3, 2)
        assert db.read_raft_state(3, 2, 0) is None
        assert db.iterate_entries(3, 2, 1, 10, 1 << 30) == []
        db.close()

    def test_cross_shard_batch_shares_stores(self, kvdb):
        fs, reopen = kvdb
        db = reopen()
        ups = [
            up(s, 1, 1, [ent(1, 1, b"s%d" % s)]) for s in range(1, 9)
        ]
        db.save_raft_state(ups, 0)
        for s in range(1, 9):
            assert db.term(s, 1, 1) == 1
        db.close()


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("batched", [True, False])
def test_kv_powerloss_fuzz(seed, batched):
    """The same kill-at-any-fsync-boundary fuzz the tan WAL passes."""
    fs = StrictMemFS()
    run_powerloss_fuzz(
        fs,
        lambda: ShardedKVLogDB(
            "/ldb", fs=fs, stores=2, batched=batched, batch_size=3,
            max_journal_bytes=600, gc_segments=1,
        ),
        seed,
    )


# ---------------------------------------------------------------------------
# live cluster on the KV backend
# ---------------------------------------------------------------------------
def test_nodehost_cluster_on_kv_backend():
    import functools

    from test_nodehost import (
        ADDRS,
        KVStore as KVStoreSM,
        make_nodehost,
        propose_r,
        reset_inproc_network,
        set_cmd,
        shard_config,
        wait_for_leader,
    )

    reset_inproc_network()
    for rid in ADDRS:
        shutil.rmtree(nh_dir(rid), ignore_errors=True)
    nhs = {
        rid: make_nodehost(rid, logdb_factory=kv_logdb_factory)
        for rid in ADDRS
    }
    try:
        for rid, nh in nhs.items():
            assert nh.logdb.name().startswith("sharded-kv")
            nh.start_replica(ADDRS, False, KVStoreSM, shard_config(rid))
        lid = wait_for_leader(nhs)
        nh = nhs[lid]
        s = nh.get_noop_session(1)
        for i in range(10):
            propose_r(nh, s, set_cmd(f"kv-{i}", bytes([i])))
        # restart a follower: the KV journal must replay it back
        fid = 1 + (lid % 3)
        nhs[fid].close()
        nhs[fid] = make_nodehost(fid, logdb_factory=kv_logdb_factory)
        nhs[fid].start_replica(ADDRS, False, KVStoreSM, shard_config(fid))
        import time

        deadline = time.time() + 10
        while time.time() < deadline:
            if nhs[fid].stale_read(1, "kv-9") == bytes([9]):
                break
            time.sleep(0.02)
        assert nhs[fid].stale_read(1, "kv-9") == bytes([9])
    finally:
        for h in nhs.values():
            h.close()
