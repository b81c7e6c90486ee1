"""Fleet-scope telemetry plane (obs/fleetscope.py + RPC_OP_OBS;
docs/OBSERVABILITY.md "Fleet scope").

Covers, per the fleet-scope tentpole:

* obs wire codec units: query/reply round-trips, empty-query defaults,
  newer-version refusal, trailing-byte strictness, the 4MB reply bound;
* trace context on RPC request frames: traced frames stamp v1 and round
  trip the ids, untraced frames stay BYTE-IDENTICAL to v0 (the
  mixed-fleet compatibility invariant);
* metrics satellite: structured ``snapshot()`` (parsed labels, monotone
  flags) and the ``export_text`` golden pin — the text exposition is a
  scrape-compatibility contract and must not drift;
* flight-recorder/tracer tails: monotone seqs, exact cursor resume
  across a forced ring wrap (``dropped`` counts the fall-off), per-
  incarnation epochs;
* ObsService + FleetScope over fake hosts: identity tagging, disabled
  planes, window deltas, merged cross-process timeline, gap open/close
  on process death, no-obs latch, restart (epoch-change) detection,
  SLO burn-rate rows with collector-mark attribution;
* the real thing over a live RpcServer: obs queries and cursor resume
  over the wire, a traced propose stitching client->server across the
  RPC boundary, the enable_obs_ops=False old-server degrade, and the
  traced-frame-at-old-server latch (tear once, go untraced, succeed);
* the 3-process SIGKILL-gap day behind ``DRAGONBOAT_MULTIPROC=1``.
"""
import json
import os
import shutil
import struct
import time
from types import SimpleNamespace

import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.audit.model import AuditKV, audit_set_cmd
from dragonboat_tpu.gateway import rpc as rpc_mod
from dragonboat_tpu.gateway.rpc import RemoteHostHandle, RpcServer
from dragonboat_tpu.metrics import MetricsRegistry
from dragonboat_tpu.obs import (
    DEFAULT_OBJECTIVES,
    FleetScope,
    FlightRecorder,
    ObsService,
    ObsUnsupported,
    Tracer,
)
from dragonboat_tpu.request import RequestError
from dragonboat_tpu.transport.inproc import reset_inproc_network
from dragonboat_tpu.transport.wire import (
    WireError,
    decode_obs_query,
    decode_obs_reply,
    decode_rpc_request,
    encode_obs_query,
    encode_obs_reply,
    encode_rpc_request,
    RpcRequest,
)


# ---------------------------------------------------------------------------
# obs wire codec units (no cluster)
# ---------------------------------------------------------------------------
class TestObsCodecs:
    def test_query_roundtrip(self):
        got = decode_obs_query(encode_obs_query(cursor=77, epoch=0xBEEF,
                                                limit=42))
        assert got == (77, 0xBEEF, 42)

    def test_empty_query_decodes_defaults(self):
        assert decode_obs_query(b"") == (0, 0, 256)

    def test_query_newer_version_rejected(self):
        buf = bytearray(encode_obs_query(cursor=1))
        struct.pack_into("<I", buf, 0, 99)
        with pytest.raises(WireError):
            decode_obs_query(bytes(buf))

    def test_query_trailing_bytes_rejected(self):
        with pytest.raises(WireError):
            decode_obs_query(encode_obs_query() + b"x")

    def test_reply_roundtrip_and_version_tag(self):
        obj = {"epoch": 5, "events": [[1, 0.5, "h", 1, "k", "d"]]}
        got = decode_obs_reply(encode_obs_reply(obj))
        assert got["v"] == 1
        assert got["epoch"] == 5 and got["events"] == obj["events"]

    def test_reply_bad_version_rejected(self):
        with pytest.raises(WireError):
            decode_obs_reply(b'{"v":99}')
        with pytest.raises(WireError):
            decode_obs_reply(b'{"no_version":1}')

    def test_reply_non_json_rejected(self):
        with pytest.raises(WireError):
            decode_obs_reply(b"\x80\x04not-json")

    def test_reply_size_bound(self):
        with pytest.raises(WireError):
            encode_obs_reply({"blob": "x" * (4 * 1024 * 1024)})
        with pytest.raises(WireError):
            decode_obs_reply(b"x" * (4 * 1024 * 1024 + 1))


class TestTraceOnRpcFrames:
    def test_untraced_request_stays_v0_byte_identical(self):
        # the compatibility invariant: no trace context -> version word
        # is 0 and NO trailing trace section (old decoders are strict
        # about trailing bytes, so same-bytes is the only safe shape).
        # The byte layout itself is pinned ONCE by the golden corpus
        # (tests/wire_goldens/rpc_request__v0.bin, wirecheck gate);
        # here we only check the invariant holds for a fresh encode.
        q = RpcRequest(req_id=3, op=1, shard_id=9, payload=b"cmd")
        buf = encode_rpc_request(q)
        assert struct.unpack_from("<I", buf, 0)[0] == 0
        d = decode_rpc_request(buf)
        assert (d.trace_id, d.span_id) == (0, 0)

    def test_v0_golden_decodes_untraced(self):
        # one source of truth: the checked-in golden IS the v0 layout
        from dragonboat_tpu.analysis.wirecheck import (
            GOLDENS_DIR,
            golden_name,
        )

        path = os.path.join(GOLDENS_DIR, golden_name("rpc_request", "v0"))
        with open(path, "rb") as f:
            buf = f.read()
        assert struct.unpack_from("<I", buf, 0)[0] == 0
        d = decode_rpc_request(buf)
        assert (d.trace_id, d.span_id) == (0, 0)
        # re-encoding the decoded request reproduces the golden exactly
        assert encode_rpc_request(d) == buf

    def test_traced_request_stamps_v1_and_roundtrips(self):
        q = RpcRequest(req_id=3, op=1, shard_id=9, payload=b"cmd",
                       trace_id=0xAB12, span_id=0xCD34)
        buf = encode_rpc_request(q)
        assert struct.unpack_from("<I", buf, 0)[0] == 1
        d = decode_rpc_request(buf)
        assert (d.trace_id, d.span_id) == (0xAB12, 0xCD34)
        assert (d.req_id, d.op, d.shard_id, d.payload) == (3, 1, 9, b"cmd")


# ---------------------------------------------------------------------------
# metrics satellite: structured snapshot + the text-format pin
# ---------------------------------------------------------------------------
def _seed_registry() -> MetricsRegistry:
    reg = MetricsRegistry(enabled=True)
    reg.counter("requests_total", labels={"op": "put"}).add(3)
    reg.counter("requests_total", labels={"op": "get"}).add(1)
    reg.gauge("queue_depth").set(7.0)
    # binary-exact observations so the _sum line is reproducible
    h = reg.histogram("latency_seconds", bounds=(0.3, 1.0))
    h.observe(0.25)
    h.observe(0.5)
    h.observe(4.0)
    return reg


class TestMetricsSnapshot:
    def test_structure_labels_and_monotone_flags(self):
        snap = _seed_registry().snapshot()
        c = snap["counters"]['requests_total{op="put"}']
        assert c["name"] == "requests_total"
        assert c["labels"] == {"op": "put"}
        assert c["value"] == 3 and c["monotone"] is True
        g = snap["gauges"]["queue_depth"]
        assert g["value"] == 7.0 and g["monotone"] is False
        h = snap["histograms"]["latency_seconds"]
        assert h["bounds"] == [0.3, 1.0]
        assert h["buckets"] == [1, 1, 1] and h["count"] == 3
        assert h["monotone"] is True
        json.dumps(snap)  # the obs reply lane is JSON — stay plain

    def test_export_text_unchanged_by_snapshot(self):
        # the golden pin: snapshot() must not perturb the Prometheus
        # exposition — scrape compatibility is byte-exact
        reg = _seed_registry()
        golden = (
            "# TYPE requests_total counter\n"
            'requests_total{op="get"} 1\n'
            'requests_total{op="put"} 3\n'
            "# TYPE queue_depth gauge\n"
            "queue_depth 7.0\n"
            "# TYPE latency_seconds histogram\n"
            'latency_seconds_bucket{le="0.3"} 1\n'
            'latency_seconds_bucket{le="1.0"} 2\n'
            'latency_seconds_bucket{le="+Inf"} 3\n'
            "latency_seconds_sum 4.75\n"
            "latency_seconds_count 3\n"
        )
        assert reg.export_text() == golden
        reg.snapshot()
        assert reg.export_text() == golden


# ---------------------------------------------------------------------------
# ring tails: monotone seqs, cursor resume, wrap, epochs
# ---------------------------------------------------------------------------
class TestRecorderTail:
    def test_cursor_resume_is_exact(self):
        rec = FlightRecorder(host="h1", capacity=64)
        for i in range(5):
            rec.record(1, "evt", f"n{i}")
        t1 = rec.tail(0, limit=2)
        assert [e[5] for e in t1["events"]] == ["n0", "n1"]
        assert t1["dropped"] == 0 and t1["seq"] == 5
        t2 = rec.tail(t1["next_cursor"], limit=2)
        assert [e[5] for e in t2["events"]] == ["n2", "n3"]
        t3 = rec.tail(t2["next_cursor"], limit=10)
        assert [e[5] for e in t3["events"]] == ["n4"]
        # drained: cursor parks at the ring head
        t4 = rec.tail(t3["next_cursor"], limit=10)
        assert t4["events"] == [] and t4["next_cursor"] == t3["next_cursor"]

    def test_seqs_are_monotone_across_rings(self):
        rec = FlightRecorder(host="h1", capacity=64)
        for sid in (1, 0, 2, 1, 0):
            rec.record(sid, "evt")
        seqs = [e[0] for e in rec.tail(0, limit=64)["events"]]
        assert seqs == sorted(seqs) == list(range(1, 6))

    def test_wrap_reports_dropped_and_resumes(self):
        rec = FlightRecorder(host="h1", capacity=4)
        for i in range(12):
            rec.record(1, "evt", f"n{i}")
        t = rec.tail(0, limit=64)
        # only the newest 4 survived the wrap; the 8 that fell off are
        # accounted for, not silently absent
        assert [e[5] for e in t["events"]] == ["n8", "n9", "n10", "n11"]
        assert t["dropped"] == 8
        # a cursor held across the wrap resumes just as exactly
        cur = rec.tail(0, limit=2)["next_cursor"]  # seq 9
        for i in range(12, 18):
            rec.record(1, "evt", f"n{i}")
        t2 = rec.tail(cur, limit=64)
        assert [e[5] for e in t2["events"]] == ["n14", "n15", "n16", "n17"]
        assert t2["dropped"] == (18 - cur) - 4

    def test_epoch_is_per_incarnation(self):
        a, b = FlightRecorder(), FlightRecorder()
        assert a.epoch and b.epoch and a.epoch != b.epoch
        assert a.tail(0, limit=1)["epoch"] == a.epoch

    def test_public_events_shape_unchanged(self):
        rec = FlightRecorder(host="h1")
        rec.record(1, "evt", "d")
        (e,) = rec.events(1)
        assert len(e) == 5 and e[1:] == ("h1", 1, "evt", "d")


class TestTracerTail:
    def test_open_spans_excluded_until_ended(self):
        tr = Tracer(host="h1", sample_rate=1.0)
        s = tr.start_trace("op", shard_id=1)
        assert tr.finished_tail(0, limit=10)["spans"] == []
        s.annotate("committed")
        s.end("ok")
        t = tr.finished_tail(0, limit=10)
        (d,) = t["spans"]
        assert d["name"] == "op" and d["status"] == "ok"
        assert d["trace_id"] == s.trace_id and d["span_id"] == s.span_id
        assert d["ann"][0][1] == "committed"
        assert t["next_cursor"] == d["seq"] == 1

    def test_cursor_resume(self):
        tr = Tracer(host="h1", sample_rate=1.0)
        for i in range(4):
            tr.start_trace(f"op{i}").end()
        t1 = tr.finished_tail(0, limit=3)
        assert [d["name"] for d in t1["spans"]] == ["op0", "op1", "op2"]
        t2 = tr.finished_tail(t1["next_cursor"], limit=3)
        assert [d["name"] for d in t2["spans"]] == ["op3"]


# ---------------------------------------------------------------------------
# ObsService + FleetScope over fake hosts (no cluster)
# ---------------------------------------------------------------------------
def _fake_nh(host="h1", nhid="nh-1", with_planes=True):
    reg = MetricsRegistry(enabled=True)
    return SimpleNamespace(
        metrics=reg,
        recorder=FlightRecorder(host=host) if with_planes else None,
        tracer=Tracer(host=host, sample_rate=1.0) if with_planes else None,
        nodehost_id=nhid,
        raft_address=lambda host=host: host,
        uptime_s=1.5,
    )


class TestObsService:
    def test_identity_tags_every_reply(self):
        svc = ObsService(_fake_nh())
        for reply in (svc.metrics_snapshot(),
                      svc.recorder_tail(0, limit=8),
                      svc.trace_spans(0, limit=8)):
            assert reply["host"] == "h1" and reply["nhid"] == "nh-1"
            assert reply["pid"] == os.getpid()
            assert reply["uptime_s"] == 1.5 and reply["mono"] > 0

    def test_disabled_planes_answer_enabled_false(self):
        svc = ObsService(_fake_nh(with_planes=False))
        rt = svc.recorder_tail(7, limit=8)
        assert rt["enabled"] is False and rt["next_cursor"] == 7
        assert rt["events"] == [] and rt["epoch"] == 0
        st = svc.trace_spans(3, limit=8)
        assert st["enabled"] is False and st["spans"] == []

    def test_tails_carry_ring_slices(self):
        nh = _fake_nh()
        nh.recorder.record(1, "leader", "r2")
        nh.tracer.start_trace("op", shard_id=1).end()
        svc = ObsService(nh)
        rt = svc.recorder_tail(0, limit=8)
        assert rt["enabled"] is True and len(rt["events"]) == 1
        st = svc.trace_spans(0, limit=8)
        assert st["enabled"] is True and len(st["spans"]) == 1


class _FlakyTarget:
    """Remote-shaped scope target (has ``obs_query``) that can be made
    unreachable or pre-obs, like a real RemoteHostHandle would be."""

    def __init__(self, nh):
        self._svc = ObsService(nh)
        self.down = False
        self.unsupported = False

    def obs_query(self, what, *, cursor=0, epoch=0, limit=256,
                  timeout=2.0):
        if self.unsupported:
            raise ObsUnsupported("unknown op 7")
        if self.down:
            raise ConnectionRefusedError("kill -9")
        if what == "metrics":
            return self._svc.metrics_snapshot()
        if what == "recorder":
            return self._svc.recorder_tail(cursor, limit=limit)
        return self._svc.trace_spans(cursor, limit=limit)


class TestFleetScope:
    def test_merges_processes_marks_and_deltas(self):
        nh1, nh2 = _fake_nh("h1", "nh-1"), _fake_nh("h2", "nh-2")
        scope = FleetScope(limit=64)
        scope.add_process("p1", nh1)
        scope.add_process("p2", nh2)
        scope.poll()  # baseline window
        nh1.recorder.record(1, "leader_changed", "r1")
        nh2.recorder.record(1, "apply", "idx=9")
        nh1.metrics.counter("gateway_committed_total").add(5)
        sp = nh1.tracer.start_trace("propose", shard_id=1)
        sp.end("ok")
        scope.mark("phase", "warmup")
        scope.poll()
        tl = scope.merged_timeline()
        kinds = [e[3] for e in tl]
        assert "leader_changed" in kinds and "apply" in kinds
        assert "phase" in kinds  # the collector mark lane
        assert "span:propose" in kinds and "span-end:propose" in kinds
        hosts = {e[1] for e in tl}
        assert {"h1", "h2", "fleetscope"} <= hosts
        # the second window carries the mark AND the counter delta
        w = scope.windows[-1]
        assert [m[3] for m in w["marks"]] == ["phase"]
        assert w["deltas"]["p1"]["counters"][
            "gateway_committed_total"] == 5
        assert scope.polls == 2

    def test_quiet_windows_cost_nothing(self):
        nh = _fake_nh()
        scope = FleetScope()
        scope.add_process("p1", nh)
        scope.poll()
        scope.poll()
        assert scope.windows[-1]["deltas"] == {}

    def test_dead_process_keeps_tail_and_marks_gap(self):
        nh = _fake_nh()
        t = _FlakyTarget(nh)
        scope = FleetScope(limit=64)
        scope.add_process("p1", t)
        nh.recorder.record(1, "pre_kill", "last words")
        scope.poll()
        t.down = True
        out = scope.poll()
        assert out["dead"] == 1
        out = scope.poll()  # still down: the gap is marked ONCE
        assert out["dead"] == 1
        kinds = [e[3] for e in scope.merged_timeline()]
        assert kinds.count("obs_gap") == 1
        assert "pre_kill" in kinds  # the dead process's tail survives
        # recovery closes the gap on the timeline
        t.down = False
        scope.poll()
        kinds = [e[3] for e in scope.merged_timeline()]
        assert "obs_gap_end" in kinds
        assert kinds.index("obs_gap") < kinds.index("obs_gap_end")
        rep = scope.proc_report()[0]
        assert rep["dead"] is False and rep["restarts"] == 0

    def test_old_process_latches_no_obs(self):
        t = _FlakyTarget(_fake_nh())
        t.unsupported = True
        scope = FleetScope()
        scope.add_process("p1", t)
        out = scope.poll()
        assert out == {"polled": 0, "dead": 0, "no_obs": 1}
        kinds = [e[3] for e in scope.merged_timeline()]
        assert "obs_gap" not in kinds  # no-obs is not a death
        assert scope.proc_report()[0]["no_obs"] is True

    def test_restart_detected_by_epoch_change(self):
        nh = _fake_nh()
        scope = FleetScope(limit=64)
        scope.add_process("p1", nh)
        nh.recorder.record(1, "before_restart")
        scope.poll()
        # the process restarts: fresh rings, fresh epoch, same address
        nh.recorder = FlightRecorder(host="h1")
        nh.tracer = Tracer(host="h1", sample_rate=1.0)
        nh.recorder.record(1, "after_restart")
        scope.poll()
        kinds = [e[3] for e in scope.merged_timeline()]
        assert "obs_restart" in kinds
        # the cursor reset refetches the NEW incarnation from seq 0
        assert "before_restart" in kinds and "after_restart" in kinds
        assert scope.proc_report()[0]["restarts"] == 1

    def test_ring_fall_off_between_polls_is_stamped(self):
        nh = _fake_nh()
        nh.recorder = FlightRecorder(host="h1", capacity=4)
        scope = FleetScope(limit=64)
        scope.add_process("p1", nh)
        scope.poll()
        for i in range(16):
            nh.recorder.record(1, "burst", f"n{i}")
        scope.poll()
        assert "obs_dropped" in [e[3] for e in scope.merged_timeline()]

    def test_slo_report_attributes_marks_to_burning_windows(self):
        nh = _fake_nh()
        scope = FleetScope()
        scope.add_process("p1", nh)
        scope.poll()
        # a kill window: sheds spike past the 5% budget
        nh.metrics.counter("gateway_shed_total", labels={"reason": "busy"}).add(30)
        nh.metrics.counter("gateway_committed_total").add(10)
        scope.mark("proc_kill", "slot=2 (leader)")
        scope.poll()
        rows = {r["objective"]: r for r in scope.slo_report()}
        assert set(rows) == {o.name for o in DEFAULT_OBJECTIVES}
        shed = rows["shed_ratio"]
        assert shed["bad"] == 30.0 and shed["good"] == 10.0
        assert shed["burning"] is True and shed["burn_rate"] > 1.0
        (w,) = shed["windows"]
        assert w["procs"] == ["p1"]
        assert [m[3] for m in w["marks"]] == ["proc_kill"]
        # objectives that never burned report clean, with empty windows
        assert rows["recovery_sla_misses"]["burning"] is False
        json.dumps(list(rows.values()))  # plain-JSON ledger

    def test_slo_mark_attribution_looks_back_a_horizon(self):
        # the kill mark lands in one short poll window but the damage
        # (timeouts, sheds) burns LATER windows during recovery — those
        # windows must still name their cause, within mark_horizon_s
        from dragonboat_tpu.obs.slo import evaluate

        def win(t0, t1, marks=(), bad=0, good=0):
            return {
                "t0": t0, "t1": t1,
                "marks": [[m_t, "fleetscope", 0, kind, ""]
                          for m_t, kind in marks],
                "deltas": {"p1": {"counters": {
                    'gateway_shed_total{reason="busy"}': bad,
                    "gateway_committed_total": good,
                }}},
            }

        windows = [
            win(10.0, 10.2, marks=[(10.1, "proc_kill")]),  # quiet, marked
            win(10.2, 13.0, bad=30, good=10),              # burns later
            win(40.0, 40.5, bad=30, good=10),              # past horizon
        ]
        rows = {r["objective"]: r for r in evaluate(windows)}
        w_burn, w_far = rows["shed_ratio"]["windows"]
        assert [m[3] for m in w_burn["marks"]] == ["proc_kill"]
        assert w_far["marks"] == []
        json.dumps(list(rows.values()))

    def test_background_poller_lifecycle(self):
        nh = _fake_nh()
        scope = FleetScope()
        scope.add_process("p1", nh)
        scope.start_poller(0.02)
        deadline = time.time() + 5
        while scope.polls < 3 and time.time() < deadline:
            time.sleep(0.02)
        scope.close()
        assert scope.polls >= 3
        n = scope.polls
        time.sleep(0.08)
        assert scope.polls == n  # poller actually stopped
        scope.close()  # idempotent
        scope.poll()   # manual sweeps still work after close


# ---------------------------------------------------------------------------
# the real thing: obs + trace stitching over a live RpcServer
# ---------------------------------------------------------------------------
def _obs_host(tag):
    reset_inproc_network()
    d = f"/tmp/nh-{tag}"
    shutil.rmtree(d, ignore_errors=True)
    nh = NodeHost(NodeHostConfig(
        nodehost_dir=d, rtt_millisecond=5, raft_address=f"{tag}-1",
        enable_tracing=True, trace_sample_rate=1.0,
        enable_flight_recorder=True,
        expert=ExpertConfig(
            engine=EngineConfig(exec_shards=1, apply_shards=1)),
    ))
    nh.start_replica(
        {1: f"{tag}-1"}, False, AuditKV,
        Config(replica_id=1, shard_id=1, election_rtt=10,
               heartbeat_rtt=1, pre_vote=True, check_quorum=True),
    )
    deadline = time.time() + 10
    while not nh.is_leader_of(1):
        assert time.time() < deadline, "no leader"
        time.sleep(0.02)
    return nh


@pytest.fixture(scope="module")
def obs_rpc_host():
    nh = _obs_host("fleetobs-e2e")
    srv = RpcServer(nh, "127.0.0.1:0")
    srv.start()
    h = RemoteHostHandle(srv.listen_address, rtt_millisecond=5,
                         tracer=Tracer(host="gateway", sample_rate=1.0))
    yield nh, srv, h
    h.close()
    srv.close()
    nh.close()


class TestObsOverRpc:
    def test_metrics_query_carries_identity(self, obs_rpc_host):
        nh, _, h = obs_rpc_host
        m = h.obs_query("metrics")
        # raft-addressed host (no gossip): nhid is empty by design
        assert m["nhid"] == str(getattr(nh, "nodehost_id", "") or "")
        assert m["host"] == nh.raft_address()
        assert m["pid"] == os.getpid() and m["bytes"] > 0
        assert "counters" in m["metrics"]

    def test_recorder_tail_resumes_over_the_wire(self, obs_rpc_host):
        nh, _, h = obs_rpc_host
        nh.recorder.record(1, "wire_evt", "a")
        nh.recorder.record(1, "wire_evt", "b")
        t1 = h.obs_query("recorder", cursor=0, limit=1)
        assert t1["enabled"] and t1["epoch"] == nh.recorder.epoch
        t2 = h.obs_query("recorder", cursor=t1["next_cursor"], limit=256)
        seen = {e[5] for e in t1["events"]} | {e[5] for e in t2["events"]}
        assert {"a", "b"} <= seen

    def test_traced_propose_stitches_across_the_boundary(
            self, obs_rpc_host):
        nh, _, h = obs_rpc_host
        s = h.sync_get_session(1, timeout=10.0)
        h.sync_propose(s, audit_set_cmd("tk", "tv"), timeout=10.0)
        s.proposal_completed()
        assert h._trace_confirmed  # a traced exchange completed
        scope = FleetScope()
        scope.add_process("server", h)  # remote: over RPC_OP_OBS
        # local target for the client-side spans (the gateway process)
        scope.add_process("gateway",
                          SimpleNamespace(tracer=h.tracer, host="gateway"))
        # server spans end on apply completion; settle then poll again
        deadline = time.time() + 10
        while scope.cross_process_stitches() < 1:
            assert time.time() < deadline, scope.dump()
            scope.poll()
            time.sleep(0.05)
        # the stitch is a real parent link, not a trace-id collision:
        # the server-side root's parent_id IS the client span's id
        for spans in scope.stitched_traces().values():
            if len({x.host for x in spans}) < 2:
                continue
            client = [x for x in spans if x.name == "rpc:propose"]
            server = [x for x in spans if x.host == nh.raft_address()]
            assert client and server
            child_parents = {x.parent_id for x in server}
            assert client[0].span_id in child_parents
            break
        h.sync_close_session(s, timeout=10.0)

    def test_propose_with_retry_threads_parent_span(self, obs_rpc_host):
        # regression: a tracer-holding handle is what propose_with_retry
        # sees during assert_recovery_sla over a ProcFleet — sync_propose
        # must accept parent= (it once raised TypeError on every retry,
        # turning each SLA probe into a guaranteed deadline exhaustion)
        from dragonboat_tpu.client import propose_with_retry

        nh, _, h = obs_rpc_host
        propose_with_retry(h, h.get_noop_session(1),
                           audit_set_cmd("pwr", "1"), timeout=10.0)
        spans = {x.name: x for x in h.tracer.spans()}
        root = spans["client:propose_with_retry"]
        hop = spans["rpc:propose"]
        assert hop.parent_id == root.span_id
        assert hop.trace_id == root.trace_id

    def test_old_server_obs_degrade(self, obs_rpc_host):
        nh, _, _ = obs_rpc_host
        old = RpcServer(nh, "127.0.0.1:0", enable_obs_ops=False)
        old.start()
        h2 = RemoteHostHandle(old.listen_address, rtt_millisecond=5)
        try:
            with pytest.raises(ObsUnsupported):
                h2.obs_query("metrics")
            scope = FleetScope()
            scope.add_process("old", h2)
            out = scope.poll()
            assert out["no_obs"] == 1
            assert scope.proc_report()[0]["no_obs"] is True
        finally:
            h2.close()
            old.close()

    def test_traced_frame_at_old_server_latches_untraced(
            self, obs_rpc_host, monkeypatch):
        nh, _, _ = obs_rpc_host
        real_decode = decode_rpc_request

        def v0_only_decode(data):
            # an old server's decoder: refuses any versioned frame
            if struct.unpack_from("<I", data, 0)[0] != 0:
                raise WireError("rpc request bin_ver 1 is newer than "
                                "supported 0")
            return real_decode(data)

        monkeypatch.setattr(rpc_mod, "decode_rpc_request", v0_only_decode)
        old = RpcServer(nh, "127.0.0.1:0")
        old.start()
        h2 = RemoteHostHandle(old.listen_address, rtt_millisecond=5,
                              tracer=Tracer(host="gw2", sample_rate=1.0))
        try:
            s = h2.sync_get_session(1, timeout=10.0)  # untraced: fine
            # first traced frame: the old server tears the connection,
            # the handle latches tracing off and the op fails DROPPED
            with pytest.raises(RequestError):
                h2.sync_propose(s, audit_set_cmd("dk", "dv"), timeout=5.0)
            assert h2._trace_disabled
            # the retry goes untraced (v0 frames) and succeeds
            h2.sync_propose(s, audit_set_cmd("dk", "dv"), timeout=10.0)
            s.proposal_completed()
            assert h2.sync_read(1, "dk", timeout=10.0) == "dv"
            h2.sync_close_session(s, timeout=10.0)
        finally:
            h2.close()
            old.close()


# ---------------------------------------------------------------------------
# the real thing: two OS processes polled over RPC_OP_OBS
# ---------------------------------------------------------------------------
def test_fleetobs_smoke_two_process_fleet():
    from dragonboat_tpu.scenario.multiproc import run_fleetobs_smoke

    # run_fleetobs_smoke itself asserts >= 1 trace stitched across the
    # RPC boundary and a plain-JSON SLO ledger with the default catalog
    out = run_fleetobs_smoke(n=2, workdir="/tmp/fleetobs-smoke-test",
                             base_port=31150)
    assert out["stitches"] >= 1, out
    assert out["polls"] >= 2 and out["reply_bytes"] > 0, out
    assert out["slo_objectives"] >= 2, out


# ---------------------------------------------------------------------------
# the 3-process SIGKILL-gap day (gated: real processes, real kill)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(os.environ.get("DRAGONBOAT_MULTIPROC") != "1",
                    reason="multi-process day: set DRAGONBOAT_MULTIPROC=1")
def test_multiproc_sigkill_gap_day():
    from dragonboat_tpu.scenario.multiproc import run_mini_multiproc_day

    # run_mini_multiproc_day itself asserts the acceptance view: the
    # SIGKILLed leader's obs_gap on the merged timeline, >=1 cross-
    # process stitch, and a non-empty SLO ledger
    rep = run_mini_multiproc_day(n=3, workdir="/tmp/fleetobs-mpday",
                                 base_port=30750)
    assert rep["audit"] == "ok"
    assert rep["obs"]["stitches"] >= 1
    assert rep["obs"]["polls"] > 0 and rep["obs"]["reply_bytes"] > 0
    rows = {r["objective"]: r for r in rep["slo"]}
    assert {"commit_p99", "shed_ratio"} <= set(rows)
    # the kill window is attributed: the proc_kill mark sits inside
    # some burning window's mark list (a real leader SIGKILL burns at
    # least one objective while the fleet re-elects)
    marks = [
        m[3]
        for r in rep["slo"]
        for w in r["windows"]
        for m in w["marks"]
    ]
    assert "proc_kill" in marks, rep["slo"]
