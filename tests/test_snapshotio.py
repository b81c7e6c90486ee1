"""Snapshot container v2: block checksums, streaming, external files.

reference: internal/rsm/snapshotio.go (SnapshotVersion, v2 block CRCs)
and statemachine.ISnapshotFileCollection [U].
"""
from __future__ import annotations

import io
import os
import struct

import pytest

from test_nodehost import nh_dir

from dragonboat_tpu.pb import CompressionType, Membership, SnapshotFile
from dragonboat_tpu.storage.snapshotio import (
    SnapshotCorruptError,
    SnapshotReader,
    SnapshotWriter,
)

MEMBERSHIP = Membership(config_change_id=5, addresses={1: "a1", 2: "a2"})


def make_container(
    data: bytes,
    *,
    block_size: int = 64,
    compression: int = 0,
    files=(),
) -> bytes:
    buf = io.BytesIO()
    w = SnapshotWriter(
        buf,
        index=42,
        term=7,
        membership=MEMBERSHIP,
        sessions=b"sessions-blob",
        on_disk=False,
        compression=compression,
        block_size=block_size,
    )
    w.write(data)
    for f in files:
        w.add_external_file(f)
    w.close()
    return buf.getvalue()


class TestRoundTrip:
    @pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 1000, 4096 + 17])
    def test_sizes(self, n):
        data = bytes(range(256)) * (n // 256 + 1)
        data = data[:n]
        blob = make_container(data)
        r = SnapshotReader(io.BytesIO(blob))
        assert r.index == 42 and r.term == 7
        assert r.membership == MEMBERSHIP
        assert r.sessions == b"sessions-blob"
        assert r.sm_size == n
        got = r.sm_stream().read(-1)
        assert got == data

    @pytest.mark.parametrize(
        "ct", [int(CompressionType.NO_COMPRESSION), int(CompressionType.ZLIB)]
    )
    def test_compression_modes(self, ct):
        data = b"A" * 100_000
        blob = make_container(data, block_size=4096, compression=ct)
        if ct:
            assert len(blob) < len(data) // 10
        r = SnapshotReader(io.BytesIO(blob))
        assert r.sm_stream().read(-1) == data
        assert r.validate() == len(data)

    def test_chunked_reads(self):
        data = os.urandom(10_000)
        blob = make_container(data, block_size=256)
        s = SnapshotReader(io.BytesIO(blob)).sm_stream()
        out = b""
        while True:
            c = s.read(37)
            if not c:
                break
            out += c
        assert out == data

    def test_external_file_table(self):
        files = [
            SnapshotFile(file_id=1, filepath="external-1-a.db",
                         file_size=100, metadata=b"meta-a"),
            SnapshotFile(file_id=2, filepath="external-2-b.db",
                         file_size=7, metadata=b""),
        ]
        blob = make_container(b"xyz", files=files)
        r = SnapshotReader(io.BytesIO(blob))
        assert r.external_files == files
        assert r.sm_stream().read(-1) == b"xyz"


class TestCorruption:
    def _flip(self, blob: bytes, off: int) -> bytes:
        b = bytearray(blob)
        b[off] ^= 0xFF
        return bytes(b)

    def test_block_corruption_detected_and_localized(self):
        data = os.urandom(64 * 5)
        blob = make_container(data, block_size=64)
        # find the 3rd block's body and corrupt one byte: the reader
        # must name block 2 (0-based) and earlier blocks must verify
        r = SnapshotReader(io.BytesIO(blob))
        s = r.sm_stream()
        # walk two blocks to find the offset of block 2
        s._next_block()
        s._next_block()
        off = s._f.tell() + 9 + 10  # header + into the body
        bad = self._flip(blob, off)
        rd = SnapshotReader(io.BytesIO(bad))
        stream = rd.sm_stream()
        assert stream.read(64) == data[:64]  # block 0 fine
        assert stream.read(64) == data[64:128]  # block 1 fine
        with pytest.raises(SnapshotCorruptError, match="block 2"):
            stream.read(64)

    def test_meta_corruption(self):
        blob = make_container(b"data")
        bad = self._flip(blob, 25)  # inside the meta blob
        with pytest.raises(SnapshotCorruptError):
            SnapshotReader(io.BytesIO(bad))

    def test_trailer_corruption(self):
        blob = make_container(b"data")
        bad = self._flip(blob, len(blob) - 6)
        with pytest.raises(SnapshotCorruptError, match="trailer"):
            SnapshotReader(io.BytesIO(bad))

    def test_table_corruption(self):
        files = [SnapshotFile(file_id=1, filepath="x", file_size=1)]
        blob = make_container(b"data", files=files)
        # table sits between sentinel and trailer
        bad = self._flip(blob, len(blob) - 30)
        with pytest.raises(SnapshotCorruptError):
            SnapshotReader(io.BytesIO(bad))

    def test_truncation(self):
        blob = make_container(os.urandom(500), block_size=64)
        for cut in (5, 20, len(blob) // 2, len(blob) - 3):
            with pytest.raises(SnapshotCorruptError):
                r = SnapshotReader(io.BytesIO(blob[:cut]))
                r.validate()

    def test_validate_counts_bytes(self):
        data = os.urandom(777)
        blob = make_container(data, block_size=100)
        assert SnapshotReader(io.BytesIO(blob)).validate() == 777


# ---------------------------------------------------------------------------
# external files end-to-end through a NodeHost (local save + boot recover)
# ---------------------------------------------------------------------------
from dragonboat_tpu.statemachine import IStateMachine


class FileBackedSM(IStateMachine):
    """IStateMachine whose state includes an external side file."""

    def __init__(self, shard_id, replica_id):
        self.kv = {}
        self.side_path = f"/tmp/sm-side-{shard_id}-{replica_id}.bin"
        self.recovered_files = []

    def update(self, entry):
        from dragonboat_tpu.statemachine import Result

        k, v = entry.cmd.decode().split("=", 1)
        self.kv[k] = v
        with open(self.side_path, "wb") as f:
            f.write(f"side:{len(self.kv)}".encode())
        return Result(value=len(self.kv))

    def lookup(self, q):
        return self.kv.get(q)

    def save_snapshot(self, w, files, done):
        import json

        if files is not None and os.path.exists(self.side_path):
            files.add_file(1, self.side_path, b"side-meta")
        w.write(json.dumps(self.kv).encode())

    def recover_from_snapshot(self, r, files, done):
        import json

        self.kv = json.loads(r.read(-1).decode())
        self.recovered_files = list(files)
        for sf in files:
            assert os.path.exists(sf.filepath), sf.filepath
            assert open(sf.filepath, "rb").read().startswith(b"side:")

    def close(self):
        pass


def test_external_files_roundtrip_through_nodehost():
    import shutil

    from test_nodehost import (
        ADDRS,
        make_nodehost,
        propose_r,
        reset_inproc_network,
        shard_config,
        wait_for_leader,
    )

    reset_inproc_network()
    for rid in ADDRS:
        shutil.rmtree(nh_dir(rid), ignore_errors=True)
    nhs = {rid: make_nodehost(rid) for rid in ADDRS}
    sms = {}

    def factory(rid):
        def f(shard_id, replica_id):
            sm = FileBackedSM(shard_id, replica_id)
            sms[replica_id] = sm
            return sm

        return f

    try:
        for rid, nh in nhs.items():
            nh.start_replica(ADDRS, False, factory(rid), shard_config(rid))
        lid = wait_for_leader(nhs)
        nh = nhs[lid]
        s = nh.get_noop_session(1)
        for i in range(5):
            propose_r(nh, s, f"k{i}=v{i}".encode())
        nh.sync_request_snapshot(1)
        ss = nh.logdb.get_snapshot(1, nh._get_node(1).replica_id)
        assert not ss.is_empty()
        # container must list the side file, staged beside snapshot.bin
        with open(ss.filepath, "rb") as f:
            rd = SnapshotReader(f)
            assert [sf.file_id for sf in rd.external_files] == [1]
            name = rd.external_files[0].filepath
        staged = os.path.join(os.path.dirname(ss.filepath), name)
        assert os.path.exists(staged)
        assert rd.external_files[0].metadata == b"side-meta"
        # restart the leader's host: boot recover must hand the SM its file
        nhs[lid].close()
        nhs[lid] = make_nodehost(lid)
        nhs[lid].start_replica(ADDRS, False, factory(lid), shard_config(lid))
        deadline_sm = sms[lid]
        assert deadline_sm.recovered_files, "recover saw no external files"
        assert deadline_sm.recovered_files[0].metadata == b"side-meta"
        assert deadline_sm.kv.get("k0") == "v0"
        # disaster recovery: export must carry the external file, import
        # must restage it, and the seeded replica must recover with it
        from dragonboat_tpu import NodeHost, NodeHostConfig, tools

        export_dir = "/tmp/ext-export"
        shutil.rmtree(export_dir, ignore_errors=True)
        tools.export_snapshot(nhs[lid], 1, export_dir)
        assert any(
            f.startswith("external-1-") for f in os.listdir(export_dir)
        ), "export dropped the external file"
        shutil.rmtree("/tmp/nh-ext-import", ignore_errors=True)
        reset_inproc_network()
        nh2 = NodeHost(
            NodeHostConfig(
                nodehost_dir="/tmp/nh-ext-import",
                rtt_millisecond=2,
                raft_address="nh-ext",
            )
        )
        try:
            tools.import_snapshot(nh2, export_dir, 1, 9, {9: "nh-ext"})
            nh2.start_replica(
                {9: "nh-ext"}, False, factory(9), shard_config(9)
            )
            import time as _t

            deadline = _t.time() + 10
            while _t.time() < deadline:
                if sms.get(9) and sms[9].recovered_files:
                    break
                _t.sleep(0.02)
            assert sms[9].recovered_files, "import lost the external file"
            assert sms[9].kv.get("k0") == "v0"
        finally:
            nh2.close()
    finally:
        for h in nhs.values():
            h.close()


def test_external_files_stream_across_hosts():
    """A follower that fell behind the compaction point restores via the
    chunk lane; the external side file must travel with the container
    and reach the follower's SM at recover (reference: chunk.go file
    chunks + ISnapshotFileCollection end-to-end [U])."""
    import shutil
    import time

    from dragonboat_tpu import settings as _settings
    from test_nodehost import (
        ADDRS,
        make_nodehost,
        propose_r,
        reset_inproc_network,
        shard_config,
        wait_for_leader,
    )

    reset_inproc_network()
    for rid in ADDRS:
        shutil.rmtree(nh_dir(rid), ignore_errors=True)
    nhs = {rid: make_nodehost(rid) for rid in ADDRS}
    sms = {}

    def factory(rid):
        def f(shard_id, replica_id):
            sm = FileBackedSM(shard_id, replica_id)
            sms[replica_id] = sm
            return sm

        return f

    # small chunks so the stream spans many chunks (true multi-chunk path)
    old_chunk = _settings.Soft.snapshot_chunk_size
    _settings.Soft.snapshot_chunk_size = 512
    try:
        for rid, nh in nhs.items():
            nh.start_replica(ADDRS, False, factory(rid), shard_config(rid))
        lid = wait_for_leader(nhs)
        nh = nhs[lid]
        s = nh.get_noop_session(1)
        # cut a follower BEFORE the entries it will need to recover
        fid = 1 + (lid % 3)
        nhs[fid].close()
        for i in range(8):
            propose_r(nh, s, f"k{i}={'v' * 400}-{i}".encode())
        # compact on EVERY live replica: otherwise an uncompacted peer
        # (or a leadership change to it) serves plain log replication and
        # the stream path never triggers
        for rid, h in nhs.items():
            if rid != fid:
                h.sync_request_snapshot(1, compaction_overhead=1)
        for i in range(3):
            propose_r(nh, s, f"post{i}=x".encode())
        # fresh follower: must restore via the streamed snapshot
        sms.pop(fid, None)
        nhf = make_nodehost(fid)
        nhs[fid] = nhf
        nhf.start_replica(ADDRS, False, factory(fid), shard_config(fid))
        deadline = time.time() + 10
        while time.time() < deadline:
            if nhf.stale_read(1, "k0") == f"{'v' * 400}-0":
                break
            time.sleep(0.02)
        assert nhf.stale_read(1, "k0") == f"{'v' * 400}-0"
        sm = sms[fid]
        assert sm.recovered_files, "follower SM saw no external files"
        assert sm.recovered_files[0].metadata == b"side-meta"
    finally:
        _settings.Soft.snapshot_chunk_size = old_chunk
        for h in nhs.values():
            h.close()


class TestBoundedBlockDecompress:
    """Regression for the wirecheck fuzz-alloc finding (PR 20): a forged
    zlib block must not expand past MAX_BLOCK_SIZE (decompression bomb),
    and a corrupt compressed stream must fail with the narrow
    SnapshotCorruptError, never a bare zlib.error."""

    @staticmethod
    def _block(body: bytes, flags: int) -> bytes:
        import zlib

        return (
            struct.pack("<I", len(body))
            + struct.pack("<I", zlib.crc32(body))
            + bytes([flags])
            + body
        )

    def test_zlib_bomb_block_rejected(self, monkeypatch):
        import zlib

        import dragonboat_tpu.storage.snapshotio as sio

        # 100k of zeros compresses to ~120B: passes the on-wire length
        # check, used to allocate the full expansion on decompress
        bomb = zlib.compress(b"\x00" * 100_000)
        monkeypatch.setattr(sio, "MAX_BLOCK_SIZE", 4096)
        stream = sio._SMStream(
            io.BytesIO(self._block(bomb, sio.BF_ZLIB)), 0, None
        )
        with pytest.raises(SnapshotCorruptError):
            stream.read()

    def test_corrupt_zlib_stream_is_narrow_error(self):
        import dragonboat_tpu.storage.snapshotio as sio

        stream = sio._SMStream(
            io.BytesIO(self._block(b"not-a-zlib-stream", sio.BF_ZLIB)),
            0,
            None,
        )
        with pytest.raises(SnapshotCorruptError):
            stream.read()

    def test_legit_zlib_block_still_decodes(self):
        import zlib

        import dragonboat_tpu.storage.snapshotio as sio

        payload = b"the-sm-bytes" * 10
        stream = sio._SMStream(
            io.BytesIO(
                self._block(zlib.compress(payload), sio.BF_ZLIB)
                + struct.pack("<I", 0)  # end sentinel
            ),
            0,
            None,
        )
        assert stream.read() == payload
