"""Observability layer (dragonboat_tpu.obs, docs/OBSERVABILITY.md).

Covers, per the observability tentpole:

* the span model + Perfetto exporter units (sampling, ring bounds,
  annotation ordering, trace_event JSON shape);
* trace-context propagation across the REAL TCP transport: a follower's
  append span parented to the leader's proposal span, stitched into one
  cross-host trace (the wire carries trace_id/span_id);
* the per-shard flight recorder: ring bounds, the EventFanout tap, and
  the AUTO-DUMP on a forced recovery-SLA violation in a nemesis run and
  on an audit-gate failure;
* satellite fixes: Prometheus label-value escaping, the
  ``event_fanout_dropped_total`` counter + named-callback warning, and
  Gauge callback exceptions exporting NaN instead of poisoning the
  scrape.
"""
import json
import math
import shutil
import threading
import time

import pytest

from dragonboat_tpu import (
    EngineConfig,
    ExpertConfig,
    Fault,
    FaultController,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.audit import (
    AuditGateError,
    AuditReport,
    assert_audit_ok,
)
from dragonboat_tpu.audit.checker import CheckResult
from dragonboat_tpu.config import ConfigError
from dragonboat_tpu.events import EventFanout
from dragonboat_tpu.faults import RecoverySLAViolation, assert_recovery_sla
from dragonboat_tpu.metrics import MetricsRegistry, _labeled
from dragonboat_tpu.obs import (
    FlightRecorder,
    Tracer,
    export_merged_json,
    format_timeline,
    hosts_timeline,
    merged_timeline,
    stitched_traces,
)
from dragonboat_tpu.pb import Message, MessageBatch, MessageType
from dragonboat_tpu.transport import wire
from dragonboat_tpu.transport.inproc import reset_inproc_network
from dragonboat_tpu.transport.tcp import tcp_transport_factory

from test_nodehost import KVStore, propose_r, set_cmd, shard_config, wait_for_leader


# ---------------------------------------------------------------------------
# span model units
# ---------------------------------------------------------------------------
class TestTracer:
    def test_trace_and_span_ids_nonzero_and_distinct(self):
        t = Tracer(host="h", seed=7)
        s = t.start_trace("propose", shard_id=3)
        assert s.trace_id and s.span_id and s.trace_id != s.span_id
        child = t.start_span("append", s.trace_id, s.span_id, shard_id=3)
        assert child.trace_id == s.trace_id
        assert child.parent_id == s.span_id

    def test_sample_rate_zero_samples_nothing(self):
        t = Tracer(sample_rate=0.0, seed=1)
        assert all(t.start_trace("p") is None for _ in range(50))
        assert t.unsampled == 50 and t.started == 0

    def test_start_span_never_samples(self):
        # a context that arrived over the wire was sampled at its root
        t = Tracer(sample_rate=0.0, seed=1)
        assert t.start_span("append", 42, 41) is not None

    def test_ring_is_bounded(self):
        t = Tracer(capacity=8, seed=1)
        for i in range(50):
            t.start_trace(f"s{i}").end()
        spans = t.spans()
        assert len(spans) == 8
        assert spans[-1].name == "s49"  # newest kept, oldest dropped

    def test_open_spans_visible_until_ended_then_gc_reclaimed(self):
        # a hung request's span must appear in dumps (status "open",
        # no span-end marker) — the auto-dump exists for exactly those
        import gc

        t = Tracer(host="h", seed=1)
        s = t.start_trace("propose", shard_id=1)
        s.annotate("request:queued")
        assert len(t.spans()) == 1
        evs = json.loads(t.export_json())["traceEvents"]
        assert any(e["args"].get("status") == "open" for e in evs)
        tl = merged_timeline(tracers=[t], shard_id=1)
        assert any(k.startswith("span:propose") for _, _, _, k, _ in tl)
        assert not any(k.startswith("span-end") for _, _, _, k, _ in tl)
        s.end("ok")
        assert len(t.spans()) == 1  # moved to the ring, not duplicated
        s2 = t.start_trace("read_index")
        del s2  # dropped without end(): weakly held, must not leak
        gc.collect()
        assert len(t.spans()) == 1

    def test_end_is_idempotent(self):
        t = Tracer(seed=1)
        s = t.start_trace("p")
        s.end(status="ok")
        first = s.end_ts
        s.end(status="later")
        assert s.end_ts == first and s.status == "ok"
        assert len(t.spans()) == 1

    def test_concurrent_end_rings_span_once(self):
        # request.py sanctions racing notifies (drop_all sweeping
        # between applied()'s lock holds) — both sides call end(); the
        # claim must be atomic or the span rings twice
        t = Tracer(seed=1)
        for _ in range(50):
            s = t.start_trace("p")
            barrier = threading.Barrier(2)

            def race():
                barrier.wait()
                s.end("ok")

            th = [threading.Thread(target=race) for _ in range(2)]
            for x in th:
                x.start()
            for x in th:
                x.join()
        assert len(t.spans()) == 50

    def test_export_json_is_valid_trace_event(self):
        t = Tracer(host="h1", seed=1)
        s = t.start_trace("propose", shard_id=2)
        s.annotate("raft:committed index=5")
        s.end()
        data = json.loads(t.export_json())
        evs = data["traceEvents"]
        complete = [e for e in evs if e["ph"] == "X"]
        instants = [e for e in evs if e["ph"] == "i"]
        assert len(complete) == 1 and len(instants) == 1
        assert complete[0]["pid"] == "h1"
        assert complete[0]["tid"] == "shard-2"
        assert complete[0]["args"]["trace_id"] == f"{s.trace_id:x}"
        assert instants[0]["name"].startswith("raft:committed")


# ---------------------------------------------------------------------------
# trace context on the wire
# ---------------------------------------------------------------------------
class TestWireTraceContext:
    def _roundtrip(self, m: Message) -> Message:
        batch = MessageBatch(messages=(m,), source_address="a:1")
        out = wire.decode_batch(wire.encode_batch(batch))
        return out.messages[0]

    def test_traced_message_roundtrips(self):
        m = Message(
            type=MessageType.REPLICATE, to=2, from_=1, shard_id=1, term=3,
            trace_id=0x1234ABCD5678, span_id=0x9FEDCBA,
        )
        r = self._roundtrip(m)
        assert r.trace_id == m.trace_id and r.span_id == m.span_id

    def test_untraced_message_roundtrips_zero(self):
        m = Message(type=MessageType.HEARTBEAT, to=2, from_=1, shard_id=1)
        r = self._roundtrip(m)
        assert r.trace_id == 0 and r.span_id == 0

    def test_future_bin_ver_rejected_v0_still_decodes(self):
        # the trace-context flag byte changed the per-message layout,
        # so the batch header is versioned: an unknown FUTURE version
        # must fail loudly (parsing it would shift fields), while the
        # known PAST version still decodes so a rolling upgrade keeps
        # talking (v0 messages simply have no flag byte to read).
        # The v0 byte layout is pinned ONCE by the golden corpus
        # (tests/wire_goldens/batch__v0.bin); the future frame comes
        # from the registry's canonical builder — no hand-built frames.
        import os

        from dragonboat_tpu.analysis import wire_registry
        from dragonboat_tpu.analysis.wirecheck import (
            GOLDENS_DIR,
            golden_name,
        )

        path = os.path.join(GOLDENS_DIR, golden_name("batch", "v0"))
        with open(path, "rb") as f:
            v0 = f.read()
        out = wire.decode_batch(v0)
        assert out.bin_ver == 0
        assert out.messages[0].trace_id == 0
        assert out.messages[0].shard_id == 1

        with pytest.raises(wire.WireError, match="newer"):
            wire.decode_batch(wire_registry.entry("batch").future())

        # re-encoding always emits the current format, whatever was read
        assert wire.decode_batch(wire.encode_batch(out)).bin_ver == 1


# ---------------------------------------------------------------------------
# flight recorder units
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_per_shard_ring_bounded(self):
        r = FlightRecorder(host="h", capacity=4)
        for i in range(20):
            r.record(1, "leader_change", f"term={i}")
        evs = r.events(1)
        assert len(evs) == 4
        assert evs[-1][4] == "term=19"

    def test_global_lane_and_merge_order(self):
        r = FlightRecorder(host="h")
        r.record(1, "park")
        r.record(0, "fault:activate", "partition")
        r.record(1, "unpark")
        kinds = [e[3] for e in r.events(1)]
        assert kinds == ["park", "fault:activate", "unpark"]  # time order
        # shard 2's view excludes shard 1's ring but sees the global lane
        assert [e[3] for e in r.events(2)] == ["fault:activate"]

    def test_dump_format(self):
        r = FlightRecorder(host="nh-1")
        r.record(3, "leader_change", "term=2 leader=1")
        line = r.dump(3).splitlines()[0]
        assert "nh-1" in line and "shard=3" in line
        assert "leader_change term=2 leader=1" in line
        assert FlightRecorder().dump() == "(flight recorder empty)"

    def test_merged_timeline_interleaves_spans(self):
        r = FlightRecorder(host="h")
        t = Tracer(host="h", seed=1)
        s = t.start_trace("propose", shard_id=1)
        r.record(1, "leader_change", "term=2")
        s.annotate("raft:committed index=1")
        s.end()
        kinds = [e[3] for e in merged_timeline(recorders=[r], tracers=[t])]
        assert kinds == [
            "span:propose", "leader_change", "ann:raft:committed index=1",
            "span-end:propose",
        ]
        assert "leader_change" in format_timeline(
            merged_timeline(recorders=[r], tracers=[t])
        )

    def test_hosts_timeline_empty_when_obs_disabled(self):
        class _NH:  # a NodeHost with observability off
            recorder = None
            tracer = None

        assert hosts_timeline([_NH(), _NH()]) == ""


# ---------------------------------------------------------------------------
# satellite fixes: metrics escaping / fanout drop counter / gauge NaN
# ---------------------------------------------------------------------------
class TestMetricsSatellites:
    def test_label_value_escaping(self):
        assert (
            _labeled("m", {"k": 'a"b\\c\nd'})
            == 'm{k="a\\"b\\\\c\\nd"}'
        )

    def test_escaped_series_exports_single_line(self):
        reg = MetricsRegistry()
        reg.counter("errs_total", {"msg": 'boom "x"\nline2'}).add()
        text = reg.export_text()
        lines = [ln for ln in text.splitlines() if ln.startswith("errs_total")]
        assert len(lines) == 1  # the newline did NOT split the series line
        assert '\\"x\\"' in lines[0] and "\\n" in lines[0]

    def test_gauge_exception_exports_nan_not_poison(self):
        reg = MetricsRegistry()
        reg.gauge("bad_gauge", fn=lambda: 1 // 0)
        reg.gauge("good_gauge", fn=lambda: 7.0)
        g = reg.gauge("bad_gauge")
        assert math.isnan(g.get())
        text = reg.export_text()  # the scrape completes
        assert "good_gauge 7.0" in text
        assert "bad_gauge nan" in text

    def test_gauge_logs_once(self):
        import logging

        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        reg = MetricsRegistry()
        g = reg.gauge("bad", fn=lambda: 1 // 0)
        lg = logging.getLogger("dragonboat_tpu.metrics")
        h = _Capture()
        lg.addHandler(h)
        try:
            g.get()
            g.get()
        finally:
            lg.removeHandler(h)
        assert len([m for m in records if "bad" in m]) == 1

    def test_fanout_drop_counter_and_named_warning(self):
        import logging

        class _Listener:
            def __init__(self):
                self.gate = threading.Event()
                self.entered = threading.Event()

            def node_ready(self, info):
                self.entered.set()
                self.gate.wait(5.0)

        records = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        reg = MetricsRegistry()
        lst = _Listener()
        fan = EventFanout(system_listener=lst, maxsize=1, metrics=reg)
        lg = logging.getLogger("dragonboat_tpu.nodehost")  # events.py's logger
        h = _Capture()
        lg.addHandler(h)
        try:
            fan.node_ready("a")  # drain thread blocks inside the callback
            assert lst.entered.wait(5.0)
            fan.node_ready("b")  # fills the queue
            before = reg.counter("event_fanout_dropped_total").value
            fan.node_ready("c")  # dropped
            assert reg.counter("event_fanout_dropped_total").value == before + 1
            assert any("node_ready" in m for m in records)
        finally:
            lg.removeHandler(h)
            lst.gate.set()
            fan.close()

    def test_fanout_close_with_full_queue_stops_drain_thread(self):
        # close()'s wake-up sentinel is dropped when the queue is full;
        # the drain thread must still exit via its timed get instead of
        # blocking forever in an untimed one and leaking past join()
        class _Slow:
            def node_ready(self, info):
                time.sleep(0.05)

        fan = EventFanout(system_listener=_Slow(), maxsize=4)
        for _ in range(32):  # saturate: sentinel put_nowait will fail
            fan.node_ready(None)
        fan.close()
        deadline = time.time() + 3.0
        while fan._thread.is_alive() and time.time() < deadline:
            time.sleep(0.05)
        assert not fan._thread.is_alive(), "drain thread leaked"

    def test_fanout_tap_sees_events_synchronously(self):
        seen = []
        fan = EventFanout(maxsize=4, tap=lambda name, args: seen.append(name))
        try:
            fan.membership_changed("info")
            assert seen == ["membership_changed"]  # before the drain thread
        finally:
            fan.close()

    def test_fanout_tap_exception_does_not_break_events(self):
        hits = []

        class _Listener:
            def node_ready(self, info):
                hits.append(info)

        def bad_tap(name, args):
            raise RuntimeError("tap bug")

        fan = EventFanout(system_listener=_Listener(), tap=bad_tap)
        try:
            fan.node_ready("x")
            deadline = time.time() + 5.0
            while not hits and time.time() < deadline:
                time.sleep(0.01)
            assert hits == ["x"]
        finally:
            fan.close()


# ---------------------------------------------------------------------------
# config gates
# ---------------------------------------------------------------------------
class TestConfigGates:
    def test_sample_rate_validated(self):
        cfg = NodeHostConfig(
            nodehost_dir="/tmp/x", raft_address="a",
            trace_sample_rate=1.5,
        )
        with pytest.raises(ConfigError):
            cfg.validate()  # NodeHost.__init__ runs this

    def test_disabled_by_default(self, tmp_path):
        nh = NodeHost(NodeHostConfig(
            nodehost_dir=str(tmp_path), raft_address="obs-gate-1",
        ))
        try:
            assert nh.tracer is None and nh.recorder is None
            assert nh.dump_timeline() == ""
            assert json.loads(nh.export_trace_json()) == {"traceEvents": []}
        finally:
            nh.close()


# ---------------------------------------------------------------------------
# cluster helpers
# ---------------------------------------------------------------------------
def _obs_config(rid, addr, tcp=False, sample_rate=1.0):
    eng = EngineConfig(exec_shards=2, apply_shards=2)
    expert = (
        ExpertConfig(engine=eng, transport_factory=tcp_transport_factory)
        if tcp
        else ExpertConfig(engine=eng)
    )
    return NodeHostConfig(
        nodehost_dir=f"/tmp/nh-obs-{rid}",
        rtt_millisecond=5,
        raft_address=addr,
        enable_tracing=True,
        trace_sample_rate=sample_rate,
        enable_flight_recorder=True,
        expert=expert,
    )


def _start_cluster(addrs, tcp=False):
    if not tcp:
        reset_inproc_network()
    nhs = {}
    for rid, addr in addrs.items():
        shutil.rmtree(f"/tmp/nh-obs-{rid}", ignore_errors=True)
        nhs[rid] = NodeHost(_obs_config(rid, addr, tcp=tcp))
    for rid, nh in nhs.items():
        nh.start_replica(addrs, False, KVStore, shard_config(rid))
    return nhs


def _close_all(nhs):
    for nh in nhs.values():
        try:
            nh.close()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass


# ---------------------------------------------------------------------------
# cross-host trace stitching over the REAL TCP transport
# ---------------------------------------------------------------------------
class TestTraceStitchTCP:
    ADDRS = {1: "127.0.0.1:27311", 2: "127.0.0.1:27312", 3: "127.0.0.1:27313"}

    def test_follower_span_parented_across_tcp(self):
        nhs = _start_cluster(self.ADDRS, tcp=True)
        try:
            wait_for_leader(nhs)
            lid, ok = nhs[1].get_leader_id(1)
            assert ok
            leader = nhs[lid]
            s = leader.get_noop_session(1)
            for i in range(5):
                propose_r(leader, s, set_cmd(f"k{i}", b"v"))

            deadline = time.time() + 10.0
            stitched = None
            while time.time() < deadline:
                by_trace = stitched_traces(nh.tracer for nh in nhs.values())
                for tid, spans in by_trace.items():
                    roots = [x for x in spans if x.name == "propose"]
                    followers = [
                        x for x in spans if x.name == "follower:append"
                    ]
                    for f in followers:
                        if any(
                            r.span_id == f.parent_id and r.host != f.host
                            for r in roots
                        ):
                            stitched = (tid, spans)
                if stitched:
                    break
                time.sleep(0.1)
            assert stitched, "no follower span parented to a leader span"
            _tid, spans = stitched
            assert len({x.host for x in spans}) >= 2  # a true cross-host trace
            # the leader root shows the full path: queue -> step -> raft
            # append -> replicate -> commit -> apply
            root = next(x for x in spans if x.name == "propose")
            labels = [a for _, a in root.annotations]
            for needle in ("request:queued", "raft:append", "raft:replicate",
                           "raft:committed", "rsm:applied"):
                assert any(needle in a for a in labels), (needle, labels)
            assert root.status == "COMPLETED"
        finally:
            _close_all(nhs)


# ---------------------------------------------------------------------------
# retransmitted REPLICATEs keep their trace context past apply (the
# ROADMAP obs gap, fixed once PR 5's randomized key bases landed): the
# leader's span-map entry survives node._complete_applied, so a
# REPLICATE re-sent to a healed follower AFTER the entry applied still
# carries the real trace_id and the follower's append leg stitches in
# ---------------------------------------------------------------------------
class TestRetransmitTraceContext:
    ADDRS = {1: "obs-rt-1", 2: "obs-rt-2", 3: "obs-rt-3"}

    def test_post_apply_retransmit_stitches_follower_append(self):
        nhs = _start_cluster(self.ADDRS)
        ctl = FaultController(seed=5)
        try:
            wait_for_leader(nhs)
            lid, ok = nhs[1].get_leader_id(1)
            assert ok
            fid = next(r for r in self.ADDRS if r != lid)
            healed_addr = self.ADDRS[fid]
            for rid, addr in self.ADDRS.items():
                ctl.install_nodehost(addr, nhs[rid])
            cut = Fault("partition", targets=(healed_addr,))
            ctl.activate(cut)
            s = nhs[lid].get_noop_session(1)
            for i in range(3):
                propose_r(nhs[lid], s, set_cmd(f"rt{i}", b"v"))
            # the proposals COMPLETED (committed + applied on the
            # quorum pair) while the partitioned follower missed every
            # REPLICATE — any append it performs after the heal is by
            # construction a post-apply retransmit
            ctl.deactivate(cut)
            deadline = time.time() + 20.0
            hit = None
            while time.time() < deadline and hit is None:
                for tid, spans in stitched_traces(
                    nh.tracer for nh in nhs.values()
                ).items():
                    roots = [x for x in spans if x.name == "propose"]
                    if not roots:
                        continue
                    for fa in spans:
                        if (
                            fa.name == "follower:append"
                            and fa.host == healed_addr
                            and any(
                                r.span_id == fa.parent_id for r in roots
                            )
                        ):
                            hit = (tid, spans)
                            break
                if hit is None:
                    time.sleep(0.1)
            assert hit, (
                "no follower:append span from the healed follower "
                "stitched into a proposal trace — the retransmitted "
                "REPLICATE went out with trace_id=0"
            )
            _tid, spans = hit
            root = next(x for x in spans if x.name == "propose")
            # the root finished BEFORE the heal could deliver anything:
            # the stitched leg is genuinely post-apply
            assert root.status == "COMPLETED"
            labels = [a for _, a in root.annotations]
            assert any("rsm:applied" in a for a in labels), labels
        finally:
            ctl.stop()
            _close_all(nhs)


# ---------------------------------------------------------------------------
# flight-recorder auto-dump on a forced SLA violation (nemesis run)
# ---------------------------------------------------------------------------
class TestAutoDump:
    ADDRS = {1: "obs-sla-1", 2: "obs-sla-2", 3: "obs-sla-3"}

    def test_sla_violation_carries_timeline(self):
        nhs = _start_cluster(self.ADDRS)
        ctl = FaultController(seed=11)
        try:
            wait_for_leader(nhs)
            for rid, addr in self.ADDRS.items():
                ctl.install_nodehost(addr, nhs[rid])
            # isolate two of the three hosts (a partition cuts edges
            # CROSSING its target set, so two singleton islands leave
            # no quorum pair): nothing can commit, the SLA trips at
            # its deadline and auto-dumps the merged recorder timeline
            ctl.activate(Fault("partition", targets=(self.ADDRS[1],)))
            ctl.activate(Fault("partition", targets=(self.ADDRS[2],)))
            with pytest.raises(RecoverySLAViolation) as ei:
                assert_recovery_sla(
                    nhs, shard_id=1, sla_ticks=300,
                    cmd=set_cmd("sla-probe", b"1"), per_try_timeout=0.5,
                )
            tl = ei.value.timeline
            assert tl, "violation did not carry the auto-dumped timeline"
            assert "fault:activate" in tl  # the nemesis action is ON the
            assert "leader_change" in tl   # same timeline as cluster state
        finally:
            ctl.stop()
            _close_all(nhs)

    def test_audit_gate_failure_carries_timeline(self):
        nhs = _start_cluster({1: "obs-gate-a"})
        try:
            wait_for_leader(nhs)
            bad = AuditReport(
                linearizability=CheckResult(ok=False),
                stale=[],
                sessions=None,
            )
            with pytest.raises(AuditGateError) as ei:
                assert_audit_ok(bad, hosts=nhs, label="test-audit")
            assert ei.value.timeline  # recorder rings attached at trip time
            assert "leader_change" in ei.value.timeline
            # passing report: no raise, no dump
            good = AuditReport(
                linearizability=CheckResult(ok=True), stale=[], sessions=None,
            )
            assert_audit_ok(good, hosts=nhs)
        finally:
            _close_all(nhs)


# ---------------------------------------------------------------------------
# the churn acceptance criterion: the injected leader-kill marker lands
# between the victim shard's last pre-kill apply span and its first
# post-re-election commit/apply annotation on ONE merged timeline
# ---------------------------------------------------------------------------
class TestChurnTimeline:
    ADDRS = {1: "obs-churn-1", 2: "obs-churn-2", 3: "obs-churn-3"}

    def test_leader_kill_between_applies_on_merged_timeline(self):
        nhs = _start_cluster(self.ADDRS)
        ctl = FaultController(seed=3)
        rev = {addr: rid for rid, addr in self.ADDRS.items()}
        try:
            wait_for_leader(nhs)
            lid, ok = nhs[1].get_leader_id(1)
            assert ok
            s = nhs[lid].get_noop_session(1)
            for i in range(5):
                propose_r(nhs[lid], s, set_cmd(f"pre{i}", b"v"))

            for rid, addr in self.ADDRS.items():
                ctl.install_nodehost(addr, nhs[rid])
            ctl.install_churn(
                {addr: nhs[rid] for rid, addr in self.ADDRS.items()},
                shards=(1,),
                kill_fn=lambda hk, sid: nhs[rev[hk]].stop_shard(sid),
                restart_fn=lambda hk, sid: None,
            )
            ctl.activate(Fault("leader_kill", targets=(1,)))
            deadline = time.time() + 10.0
            while time.time() < deadline:
                if any(nh._nodes.get(1) is None for nh in nhs.values()):
                    break
                time.sleep(0.05)
            survivors = {
                r: nh for r, nh in nhs.items() if nh._nodes.get(1) is not None
            }
            assert len(survivors) == 2, "leader_kill did not stop a shard"
            wait_for_leader(survivors, timeout=20.0)
            lid2 = None
            deadline = time.time() + 20.0
            while time.time() < deadline:  # a survivor must WIN, not
                lid, ok = next(iter(survivors.values())).get_leader_id(1)
                if ok and lid in survivors:  # just echo the dead leader
                    lid2 = lid
                    break
                time.sleep(0.05)
            assert lid2 is not None, "no surviving replica took leadership"
            s2 = nhs[lid2].get_noop_session(1)
            propose_r(nhs[lid2], s2, set_cmd("post", b"v"))

            tl = merged_timeline(
                recorders=[nh.recorder for nh in nhs.values()],
                tracers=[nh.tracer for nh in nhs.values()],
                shard_id=1,
            )
            kills = [
                i for i, e in enumerate(tl)
                if e[3].startswith("churn:leader_kill:kill")
            ]
            assert kills, [e[3] for e in tl]
            k = kills[0]
            assert any(
                e[3].startswith("ann:rsm:applied") for e in tl[:k]
            ), "no pre-kill apply span annotation before the kill marker"
            assert any(
                e[3].startswith("ann:raft:committed")
                or e[3].startswith("ann:rsm:applied")
                for e in tl[k + 1:]
            ), "no post-re-election commit/apply after the kill marker"
            # the re-election itself is on the same timeline
            assert any(
                e[3] == "leader_change" for e in tl[k + 1:]
            ), "no leader_change after the kill marker"
        finally:
            ctl.stop()
            _close_all(nhs)


# ---------------------------------------------------------------------------
# NodeHost surface: dump_timeline / export / engine gauges
# ---------------------------------------------------------------------------
class TestNodeHostSurface:
    ADDRS = {1: "obs-nhs-1", 2: "obs-nhs-2", 3: "obs-nhs-3"}

    def test_dump_export_and_gauges(self, tmp_path):
        nhs = _start_cluster(self.ADDRS)
        try:
            wait_for_leader(nhs)
            lid, ok = nhs[1].get_leader_id(1)
            assert ok
            leader = nhs[lid]
            s = leader.get_noop_session(1)
            for i in range(3):
                propose_r(leader, s, set_cmd(f"d{i}", b"v"))

            out = leader.dump_timeline(shard_id=1)
            assert "span:propose" in out and "leader_change" in out

            path = str(tmp_path / "trace.json")
            data = json.loads(leader.export_trace_json(path))
            assert data["traceEvents"]
            assert json.load(open(path)) == data

            # the cluster-wide forms: one parseable trace_event export
            # over every host's tracer, and one merged timeline that
            # saw the election
            merged = json.loads(
                export_merged_json([nh.tracer for nh in nhs.values()])
            )
            assert merged["traceEvents"]
            assert "leader_change" in hosts_timeline(nhs.values())

            # engine gauges exist and scrape cleanly (values are racy
            # by design; the scrape itself must not throw)
            assert leader._queue_depth_total() >= 0
            assert leader._tick_lag_max() >= 0
            assert leader._apply_lag_max() >= 0
        finally:
            _close_all(nhs)

    def test_sampling_bounds_trace_volume(self):
        reset_inproc_network()
        shutil.rmtree("/tmp/nh-obs-s1", ignore_errors=True)
        cfg = _obs_config(1, "obs-sample-1", sample_rate=0.0)
        cfg.nodehost_dir = "/tmp/nh-obs-s1"
        nh = NodeHost(cfg)
        try:
            nh.start_replica(
                {1: "obs-sample-1"}, False, KVStore, shard_config(1)
            )
            wait_for_leader({1: nh})
            s = nh.get_noop_session(1)
            for i in range(5):
                propose_r(nh, s, set_cmd(f"u{i}", b"v"))
            assert nh.tracer.started == 0
            assert nh.tracer.unsampled >= 5
            assert not nh.tracer.spans()
        finally:
            nh.close()
