"""Aux subsystem tests: metrics, NodeHostID, gossip registry, snapshot
export/import (disaster recovery), per SURVEY.md §5.
"""
import io
import os
import pickle
import shutil
import time

import pytest

from dragonboat_tpu import (
    EngineConfig,
    ExpertConfig,
    GossipConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu import tools
from dragonboat_tpu.id import get_nodehost_id, is_nodehost_id
from dragonboat_tpu.metrics import MetricsRegistry
from dragonboat_tpu.transport.gossip import GossipManager, GossipRegistry
from dragonboat_tpu.transport.tcp import tcp_transport_factory

from test_nodehost import nh_dir  # noqa: F401
from test_nodehost import (
    ADDRS,
    KVStore,
    make_nodehost,
    propose_r,
    set_cmd,
    shard_config,
    wait_for_leader,
)
from dragonboat_tpu.transport.inproc import reset_inproc_network


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_gauge_histogram_export(self):
        reg = MetricsRegistry()
        reg.counter("a_total").add(3)
        reg.gauge("b_current").set(1.5)
        reg.gauge("c_fn", lambda: 7)
        with reg.timer("d_seconds"):
            pass
        text = reg.export_text()
        assert "# TYPE a_total counter\na_total 3" in text
        assert "b_current 1.5" in text
        assert "c_fn 7" in text
        assert "d_seconds_count 1" in text
        assert 'd_seconds_bucket{le="+Inf"} 1' in text

    def test_disabled_registry_is_noop(self):
        reg = MetricsRegistry(enabled=False)
        reg.counter("x").add()
        reg.gauge("y").set(1)
        assert reg.export_text() == "\n"

    def test_nodehost_health_metrics(self):
        reset_inproc_network()
        for rid in ADDRS:
            shutil.rmtree(nh_dir(rid), ignore_errors=True)
        nhs = {}
        try:
            for rid in ADDRS:
                cfg = NodeHostConfig(
                    nodehost_dir=nh_dir(rid),
                    rtt_millisecond=2,
                    raft_address=ADDRS[rid],
                    enable_metrics=True,
                    expert=ExpertConfig(
                        engine=EngineConfig(exec_shards=2, apply_shards=2)
                    ),
                )
                nhs[rid] = NodeHost(cfg)
            for rid, nh in nhs.items():
                nh.start_replica(ADDRS, False, KVStore, shard_config(rid))
            wait_for_leader(nhs)
            s = nhs[1].get_noop_session(1)
            propose_r(nhs[1], s, set_cmd("m", b"1"))
            w = io.StringIO()
            nhs[1].write_health_metrics(w)
            text = w.getvalue()
            assert "raft_nodehost_shards 1" in text
            assert "raft_engine_step_seconds_count" in text
            assert "raft_transport_sent_total" in text
        finally:
            for nh in nhs.values():
                nh.close()


# ---------------------------------------------------------------------------
# nodehost id
# ---------------------------------------------------------------------------
class TestNodeHostID:
    def test_persistent(self, tmp_path):
        a = get_nodehost_id(str(tmp_path))
        assert is_nodehost_id(a)
        assert get_nodehost_id(str(tmp_path)) == a

    def test_distinct_dirs(self, tmp_path):
        a = get_nodehost_id(str(tmp_path / "a"))
        b = get_nodehost_id(str(tmp_path / "b"))
        assert a != b


# ---------------------------------------------------------------------------
# gossip
# ---------------------------------------------------------------------------
class TestGossip:
    def test_convergence_and_update(self):
        managers = []
        try:
            seed = GossipManager("nhid-seed", "raft-seed:1", "127.0.0.1:0", [])
            seed.start()
            managers.append(seed)
            for i in range(2):
                m = GossipManager(
                    f"nhid-m{i}",
                    f"raft-m{i}:1",
                    "127.0.0.1:0",
                    [seed.bind_address],
                    interval=0.05,
                )
                m.start()
                managers.append(m)
            deadline = time.time() + 5.0
            while time.time() < deadline:
                tables = [m.table() for m in managers]
                if all(len(t) == 3 for t in tables):
                    break
                time.sleep(0.05)
            else:
                raise AssertionError(f"no convergence: {tables}")
            # address change propagates (version bump wins)
            managers[1].set_raft_address("raft-m0-moved:9")
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if seed.lookup("nhid-m0") == "raft-m0-moved:9":
                    break
                time.sleep(0.05)
            else:
                raise AssertionError(seed.table())
        finally:
            for m in managers:
                m.close()

    def test_restart_refutes_stale_own_address(self):
        """A restarted host re-seeds its row at version 1 while peers
        gossip the old address at a higher version; the node must refute
        rather than adopt its own stale address (code-review finding)."""
        managers = []
        try:
            a = GossipManager("nhid-a", "addr-old:1", "127.0.0.1:0", [], interval=0.05)
            a.start()
            managers.append(a)
            b = GossipManager(
                "nhid-b", "addr-b:1", "127.0.0.1:0", [a.bind_address], interval=0.05
            )
            b.start()
            managers.append(b)
            deadline = time.time() + 5.0
            while time.time() < deadline and len(b.table()) < 2:
                time.sleep(0.05)
            # bump a's version a few times so b holds (addr-old, high ver)
            for _ in range(3):
                a.set_raft_address("addr-old:1")
            time.sleep(0.3)
            # "restart" a with a NEW address at version 1
            a.close()
            managers.remove(a)
            a2 = GossipManager(
                "nhid-a", "addr-new:9", a.bind_address, [b.bind_address],
                interval=0.05,
            )
            a2.start()
            managers.append(a2)
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if (
                    a2.lookup("nhid-a") == "addr-new:9"
                    and b.lookup("nhid-a") == "addr-new:9"
                ):
                    break
                time.sleep(0.05)
            else:
                raise AssertionError(
                    f"stale address won: a2={a2.lookup('nhid-a')} b={b.lookup('nhid-a')}"
                )
        finally:
            for m in managers:
                m.close()

    def test_registry_translation(self):
        mgr = GossipManager("nhid-x", "10.0.0.1:100", "127.0.0.1:0", [])
        try:
            mgr.start()
            reg = GossipRegistry(mgr)
            reg.add(1, 1, "nhid-x")       # value is a nodehost id
            reg.add(1, 2, "10.0.0.2:200")  # plain address passes through
            assert reg.resolve(1, 1) == "10.0.0.1:100"
            assert reg.resolve(1, 2) == "10.0.0.2:200"
            assert reg.resolve(1, 3) is None
        finally:
            mgr.close()

    def test_learn_never_clobbers_nodehost_id(self):
        """Learning a sender address from traffic must not replace a
        NodeHostID mapping — that would pin the peer to its current host
        and defeat the gossip indirection (advisor finding)."""
        import tempfile

        from dragonboat_tpu.transport.registry import Registry

        with tempfile.TemporaryDirectory() as d:
            nhid = get_nodehost_id(d)
        reg = Registry()
        reg.add(1, 1, nhid)
        reg.learn(1, 1, "10.0.0.9:900")
        assert reg.resolve(1, 1) == nhid  # untouched
        reg.add(1, 2, "10.0.0.2:200")
        reg.learn(1, 2, "10.0.0.9:900")  # plain addr: updated
        assert reg.resolve(1, 2) == "10.0.0.9:900"
        reg.learn(1, 3, "10.0.0.3:300")  # unknown: learned
        assert reg.resolve(1, 3) == "10.0.0.3:300"

    def test_push_packets_shard_large_tables(self):
        """The full-table push must stay under the UDP packet bound by
        sharding rows across packets, each independently decodable and
        carrying the sender row (advisor finding)."""
        from dragonboat_tpu.transport.gossip import (
            MAX_PACKET,
            _decode_table,
            _encode_packets,
        )

        table = {
            f"nhid-{i:05d}" + "x" * 40: (f"10.0.{i // 256}.{i % 256}:7000", i)
            for i in range(2000)
        }
        pkts = _encode_packets(table, "1.2.3.4:99")
        assert len(pkts) > 1
        merged = {}
        for p in pkts:
            assert len(p) <= MAX_PACKET
            t = _decode_table(p)
            assert t is not None
            assert t.pop("__sender__") == ("1.2.3.4:99", 0)
            merged.update(t)
        assert merged == table


# ---------------------------------------------------------------------------
# nodehost-id addressing end to end (TCP + gossip)
# ---------------------------------------------------------------------------
NHID_PORTS = {1: 27401, 2: 27402, 3: 27403}


@pytest.fixture
def nhid_cluster():
    for rid in NHID_PORTS:
        shutil.rmtree(f"/tmp/nh-id-{rid}", ignore_errors=True)
    nhs = {}
    seed = f"127.0.0.1:{28400 + 1}"
    for rid, port in NHID_PORTS.items():
        cfg = NodeHostConfig(
            nodehost_dir=f"/tmp/nh-id-{rid}",
            rtt_millisecond=5,
            raft_address=f"127.0.0.1:{port}",
            address_by_nodehost_id=True,
            gossip=GossipConfig(
                bind_address=f"127.0.0.1:{28400 + rid}",
                seed=[seed],
            ),
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=2, apply_shards=2),
                transport_factory=tcp_transport_factory,
            ),
        )
        nhs[rid] = NodeHost(cfg)
    yield nhs
    for nh in nhs.values():
        nh.close()


class TestNodeHostIDAddressing:
    def test_cluster_by_nodehost_id(self, nhid_cluster):
        nhs = nhid_cluster
        members = {rid: nh.nodehost_id for rid, nh in nhs.items()}
        for rid, nh in nhs.items():
            nh.start_replica(members, False, KVStore, shard_config(rid))
        wait_for_leader(nhs, timeout=10.0)
        s = nhs[1].get_noop_session(1)
        propose_r(nhs[1], s, set_cmd("gk", b"gv"))
        deadline = time.time() + 10.0
        while True:
            try:
                assert nhs[3].sync_read(1, "gk", timeout=2.0) == b"gv"
                break
            except AssertionError:
                raise
            except Exception:
                if time.time() > deadline:
                    raise
                time.sleep(0.05)


# ---------------------------------------------------------------------------
# snapshot export / import
# ---------------------------------------------------------------------------
class TestExportImport:
    def test_export_then_import_new_membership(self, tmp_path):
        reset_inproc_network()
        for rid in ADDRS:
            shutil.rmtree(nh_dir(rid), ignore_errors=True)
        nhs = {rid: make_nodehost(rid) for rid in ADDRS}
        export_dir = str(tmp_path / "export")
        try:
            for rid, nh in nhs.items():
                nh.start_replica(ADDRS, False, KVStore, shard_config(rid))
            wait_for_leader(nhs)
            s = nhs[1].get_noop_session(1)
            for i in range(5):
                propose_r(nhs[1], s, set_cmd(f"e-{i}", str(i).encode()))
            nhs[1].sync_request_snapshot(1)
            ss = tools.export_snapshot(nhs[1], 1, export_dir)
            assert ss.index > 0
        finally:
            for nh in nhs.values():
                nh.close()

        # disaster: all replicas lost; rebuild a 1-replica shard from the
        # export on a fresh nodehost with a rewritten membership
        reset_inproc_network()
        shutil.rmtree("/tmp/nh-import", ignore_errors=True)
        cfg = NodeHostConfig(
            nodehost_dir="/tmp/nh-import",
            rtt_millisecond=2,
            raft_address="nh-import",
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=2, apply_shards=2)
            ),
        )
        nh = NodeHost(cfg)
        try:
            members = {9: "nh-import"}
            imported = tools.import_snapshot(nh, export_dir, 1, 9, members)
            assert imported.imported
            nh.start_replica(members, False, KVStore, shard_config(9))
            deadline = time.time() + 10.0
            while True:
                try:
                    assert nh.sync_read(1, "e-4", timeout=2.0) == b"4"
                    break
                except AssertionError:
                    raise
                except Exception:
                    if time.time() > deadline:
                        raise
                    time.sleep(0.05)
            # the rebuilt shard accepts new writes under the new membership
            s = nh.get_noop_session(1)
            propose_r(nh, s, set_cmd("post-import", b"1"))
            assert nh.sync_read(1, "post-import", timeout=5.0) == b"1"
        finally:
            nh.close()


# ---------------------------------------------------------------------------
# snapshot compression
# ---------------------------------------------------------------------------
class TestSnapshotCompression:
    def test_compressed_snapshot_save_stream_recover(self):
        """Compression is recorded in the snapshot meta and survives all
        three consumers: boot recover, streamed install, export/import."""
        import zlib

        from dragonboat_tpu import Config
        from dragonboat_tpu.pb import CompressionType

        reset_inproc_network()
        for rid in ADDRS:
            shutil.rmtree(nh_dir(rid), ignore_errors=True)
        nhs = {rid: make_nodehost(rid) for rid in ADDRS}

        def comp_config(rid):
            c = shard_config(rid)
            c.snapshot_compression = int(CompressionType.ZLIB)
            return c

        try:
            for rid, nh in nhs.items():
                nh.start_replica(ADDRS, False, KVStore, comp_config(rid))
            lid = wait_for_leader(nhs)
            nh = nhs[lid]
            s = nh.get_noop_session(1)
            # cut off a follower FIRST (a replica that loses acked state is
            # outside raft's model — same as the reference; the streamed
            # snapshot path serves replicas that fell behind the compaction
            # point, so the follower must go down before these entries)
            fid = 1 + (lid % 3)
            nhs[fid].close()
            # compressible payload
            for i in range(20):
                propose_r(nh, s, set_cmd(f"z-{i}", b"A" * 2000))
            nh.sync_request_snapshot(1, compaction_overhead=1)
            ss = nh.logdb.get_snapshot(1, nh._get_node(1).replica_id)
            assert ss.compression == CompressionType.ZLIB
            # v2 container: per-block compression, self-describing
            from dragonboat_tpu.storage.snapshotio import SnapshotReader

            with open(ss.filepath, "rb") as f:
                rd = SnapshotReader(f)
                assert rd.compression == int(CompressionType.ZLIB)
                sm_size = rd.validate()  # every block checksum verified
            assert sm_size >= 20 * 2000  # logical payload
            assert os.path.getsize(ss.filepath) < sm_size  # compressed
            for i in range(3):
                propose_r(nh, s, set_cmd(f"zp-{i}", b"v"))
            # fresh follower must restore via the compressed snapshot stream
            nhf = make_nodehost(fid)
            nhs[fid] = nhf
            nhf.start_replica(ADDRS, False, KVStore, comp_config(fid))
            deadline = time.time() + 10
            while time.time() < deadline:
                if nhf.stale_read(1, "z-0") == b"A" * 2000:
                    break
                time.sleep(0.02)
            assert nhf.stale_read(1, "z-0") == b"A" * 2000
            # export/import keeps the compression type
            export_dir = f"/tmp/comp-export"
            shutil.rmtree(export_dir, ignore_errors=True)
            tools.export_snapshot(nh, 1, export_dir)
        finally:
            for h in nhs.values():
                h.close()
        shutil.rmtree("/tmp/nh-comp-import", ignore_errors=True)
        reset_inproc_network()
        nh2 = NodeHost(
            NodeHostConfig(
                nodehost_dir="/tmp/nh-comp-import",
                rtt_millisecond=2,
                raft_address="nh-ci",
            )
        )
        try:
            imported = tools.import_snapshot(nh2, export_dir, 1, 9, {9: "nh-ci"})
            assert imported.compression == CompressionType.ZLIB
            nh2.start_replica({9: "nh-ci"}, False, KVStore, shard_config(9))
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    if nh2.stale_read(1, "z-19") == b"A" * 2000:
                        break
                except Exception:
                    pass
                time.sleep(0.05)
            assert nh2.stale_read(1, "z-19") == b"A" * 2000
        finally:
            nh2.close()


# ---------------------------------------------------------------------------
# rate limiting
# ---------------------------------------------------------------------------
class TestRateLimits:
    def test_max_in_mem_log_size_system_busy(self):
        """Proposals are refused with SystemBusy while the in-mem log
        window exceeds MaxInMemLogSize (reference: ErrSystemBusy [U])."""
        from dragonboat_tpu import SystemBusy
        from dragonboat_tpu.raft.raft import Raft
        from dragonboat_tpu.pb import Entry, Message, MessageType

        r = Raft(
            shard_id=1, replica_id=1, peers={1: "a", 2: "b", 3: "c"},
            max_in_mem_log_size=65536,
        )
        assert not r.rate_limited()
        # stuff the in-mem window way past the limit
        big = [
            Entry(term=1, index=i, cmd=b"x" * 8192) for i in range(1, 20)
        ]
        r.log.inmem.merge(big)
        assert r.rate_limited()
        # draining (persist + apply) clears the signal
        r.log.inmem.saved_log_to(19, 1)
        r.log.inmem.applied_log_to(19)
        assert not r.rate_limited()

    def test_nodehost_propose_system_busy(self):
        from dragonboat_tpu import SystemBusy
        from dragonboat_tpu.pb import Entry

        reset_inproc_network()
        for rid in ADDRS:
            shutil.rmtree(nh_dir(rid), ignore_errors=True)
        nhs = {rid: make_nodehost(rid) for rid in ADDRS}
        try:
            for rid, nh in nhs.items():
                cfg = shard_config(rid)
                cfg.max_in_mem_log_size = 65536
                nh.start_replica(ADDRS, False, KVStore, cfg)
            wait_for_leader(nhs)
            node = nhs[1]._nodes[1]
            # force the window over the limit from the outside
            node.peer.raft.log.inmem.merge(
                [Entry(term=1, index=node.peer.raft.log.last_index() + 1,
                       cmd=b"x" * 100000)]
            )
            s = nhs[1].get_noop_session(1)
            with pytest.raises(SystemBusy):
                nhs[1].sync_propose(s, set_cmd("k", b"v"), timeout=1.0)
        finally:
            for nh in nhs.values():
                nh.close()

    def test_snapshot_send_rate_cap(self):
        """The chunk stream is paced to MaxSnapshotSendBytesPerSecond."""
        import time as _t

        from dragonboat_tpu.pb import Chunk, Message, MessageType, Snapshot
        from dragonboat_tpu.transport.transport import Transport
        from dragonboat_tpu.transport.inproc import InProcTransport

        reset_inproc_network()
        got = []
        rx = InProcTransport("rate-rx", lambda b: None, lambda c: got.append(c) or True)
        rx.start()
        tx_raw = InProcTransport("rate-tx", lambda b: None, None)
        # shrink chunks so the stream spans several pacing rounds
        from dragonboat_tpu import settings as _settings

        old_chunk = _settings.Soft.snapshot_chunk_size
        _settings.Soft.snapshot_chunk_size = 8192
        payload = b"z" * 40000
        from test_transport import BytesSource

        tx = Transport(
            tx_raw,
            lambda s, r: "rate-rx",
            "rate-tx",
            snapshot_source_opener=lambda ss: BytesSource(payload),
            max_snapshot_send_bytes_per_second=80000,  # ~0.5s for 40KB
        )
        tx.start()
        try:
            ss = Snapshot(filepath="/x", file_size=len(payload), index=5,
                          term=1, shard_id=1, replica_id=2)
            m = Message(type=MessageType.INSTALL_SNAPSHOT, to=2, from_=1,
                        shard_id=1, term=1, snapshot=ss)
            t0 = _t.monotonic()
            assert tx.send_snapshot(m)
            deadline = _t.monotonic() + 5
            while _t.monotonic() < deadline and (
                not got or sum(len(c.data) for c in got) < len(payload)
            ):
                _t.sleep(0.01)
            dt = _t.monotonic() - t0
            assert sum(len(c.data) for c in got) >= len(payload)
            assert dt >= 0.3, f"stream not paced: {dt:.2f}s"
        finally:
            _settings.Soft.snapshot_chunk_size = old_chunk
            tx.close()
            rx.close()

    def test_quiesce_hint_respects_exit_grace(self):
        """A node inside its exit-grace window must not adopt a peer's
        enter-hint (a half-quiesced node runs live timers while flagged
        quiesced — review finding)."""
        from dragonboat_tpu.pb import MessageType
        from dragonboat_tpu.raft.quiesce import QuiesceManager

        q = QuiesceManager(enabled=True, election_timeout=10)  # threshold 100
        for _ in range(100):
            q.tick()
        assert q.is_quiesced()
        q.record_activity(MessageType.PROPOSE)  # wake: grace = 100
        assert not q.is_quiesced() and q.exit_grace > 0
        for _ in range(60):
            q.tick()  # idle_ticks back over threshold//2, grace remains
        q.quiesce_hint()
        assert not q.is_quiesced()  # hint refused during grace
        # reset idle mid-grace so idle lands in [threshold//2, threshold)
        # when the grace expires — exercising the acceptance branch (not
        # tick()'s own threshold re-entry)
        q.record_activity(MessageType.PROPOSE)
        for _ in range(55):
            q.tick()  # grace (40 left) drains; idle = 55
        assert q.exit_grace == 0 and 50 <= q.idle_ticks < 100
        assert not q.is_quiesced()
        q.quiesce_hint()
        assert q.is_quiesced()  # honored: idle >= threshold//2, no grace

    def test_quiesce_block_never_enters(self):
        """``block=True`` (no known leader) must prevent quiesce entry
        UNBOUNDEDLY — the 3-window busy give-up would re-park a shard
        still mid-election (r5 finding: colocated election traffic is
        device-routed and invisible to the manager, so a leaderless
        shard hit the idle threshold while electing, parked, and slept
        forever)."""
        from dragonboat_tpu.raft.quiesce import QuiesceManager

        q = QuiesceManager(enabled=True, election_timeout=10)  # threshold 100
        for _ in range(10 * q.threshold):  # far past the 3-window hold
            assert not q.tick(block=True)
        assert not q.is_quiesced() and q.idle_ticks == 0
        # leader appears -> ordinary idle accounting resumes
        for _ in range(q.threshold):
            q.tick()
        assert q.is_quiesced()

    def test_leaderless_node_never_quiesces(self):
        """node.step_with_inputs' tick path: a raft node with no known
        leader must not enter quiesce no matter how long it idles (its
        own campaigns are outbound and never count as activity)."""
        from test_nodehost import KVStore, make_nodehost, shard_config
        from dragonboat_tpu.transport.inproc import reset_inproc_network
        import shutil as _sh

        reset_inproc_network()
        for rid in (1,):
            _sh.rmtree(nh_dir(rid), ignore_errors=True)
        nh = make_nodehost(1)
        try:
            # two-member shard with only ONE member started: quorum is
            # unreachable, so the node campaigns forever with no leader
            nh.start_replica(
                {1: "nh-1", 2: "nh-2"}, False, KVStore,
                shard_config(1, quiesce=True, election_rtt=10),
            )
            node = nh._nodes[1]
            deadline = time.time() + 8.0
            while time.time() < deadline:
                assert not node.quiesce.is_quiesced()
                assert 1 not in nh._parked
                time.sleep(0.2)
            # it kept electing the whole time (terms advanced)
            assert node.peer.raft.term >= 2
        finally:
            nh.close()
