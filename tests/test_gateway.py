"""Serving front plane (dragonboat_tpu.gateway, docs/GATEWAY.md).

Covers, per the gateway tentpole:

* RoutingCache units: copy-on-write snapshot reads, event-tap
  learn/invalidate from ``leader_updated``/``balance_move_*``, bulk
  refresh from a balance ClusterView, discovery fallback;
* AdmissionController units: bounded per-shard queue, deadline-aware
  shed via ``LatencyBudget.can_meet``, depth accounting, the
  sustained-shed dump trigger;
* Gateway end-to-end on a 3-host in-proc cluster: session handles with
  per-session ordering, batched submission, exactly-once results;
* leader-lease reads: the fast path under CheckQuorum, fallback when
  ``check_quorum`` is off, and the SAFETY cases — leader transfer and
  leader kill mid-lease force fallback to ReadIndex (no stale read
  past lease expiry), with an ``audit/`` stale-read containment pass
  over a gateway read/write history under leader-kill churn;
* overload: a flooded tiny-queue gateway sheds (``gateway_shed_total``
  > 0), completes everything it admits, and auto-dumps the flight
  recorder on sustained shedding.
"""
import json
import shutil
import threading
import time

import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    Gateway,
    GatewayBusy,
    GatewayClosed,
    GatewayConfig,
    LatencyBudget,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.audit.checker import check_stale_reads
from dragonboat_tpu.audit.history import HistoryRecorder
from dragonboat_tpu.audit.model import AuditKV, audit_set_cmd
from dragonboat_tpu.balance.view import ClusterView, ReplicaView, ShardView
from dragonboat_tpu.events import EventFanout
from dragonboat_tpu.gateway import AdmissionController, RoutingCache
from dragonboat_tpu.metrics import MetricsRegistry
from dragonboat_tpu.raftio import LeaderInfo
from dragonboat_tpu.transport.inproc import reset_inproc_network

from test_nodehost import KVStore, set_cmd


# ---------------------------------------------------------------------------
# routing cache units
# ---------------------------------------------------------------------------
class TestRoutingCache:
    def test_learn_lookup_invalidate_snapshot_discipline(self):
        rc = RoutingCache(lambda: {})
        assert rc.lookup(1) is None
        rc.learn(1, "h-a")
        t0 = rc._table
        assert rc.lookup(1) == "h-a"
        rc.learn(2, "h-b")
        # copy-on-write: the old snapshot object is untouched
        assert t0 == {1: "h-a"} and rc.lookup(2) == "h-b"
        rc.invalidate(1)
        assert rc.lookup(1) is None and rc.lookup(2) == "h-b"
        rc.invalidate(99)  # absent: no-op, no error
        rc.invalidate_all()
        assert rc.table() == {}

    def test_leader_updated_tap_learns_and_invalidates(self):
        rc = RoutingCache(lambda: {})
        tap_a = rc.host_tap("h-a")
        # the leader's own observation learns the route
        tap_a("leader_updated", (LeaderInfo(1, replica_id=3, term=2,
                                            leader_id=3),))
        assert rc.lookup(1) == "h-a"
        # a follower learning some other leader cannot map it: ignored
        tap_b = rc.host_tap("h-b")
        tap_b("leader_updated", (LeaderInfo(1, replica_id=2, term=2,
                                            leader_id=3),))
        assert rc.lookup(1) == "h-a"
        # leaderless observation invalidates
        tap_b("leader_updated", (LeaderInfo(1, replica_id=2, term=3,
                                            leader_id=0),))
        assert rc.lookup(1) is None

    def test_balance_move_events_invalidate(self):
        rc = RoutingCache(lambda: {})
        rc.learn(7, "h-a")
        tap = rc.host_tap("h-a")

        class Info:
            shard_id = 7

        tap("balance_move_started", (Info(),))
        assert rc.lookup(7) is None

    def test_refresh_from_view_bulk_updates(self):
        rc = RoutingCache(lambda: {})
        rc.learn(1, "stale-host")
        view = ClusterView(
            hosts=("h-a", "h-b"),
            draining=(),
            shards=(
                ShardView(
                    shard_id=1,
                    members=((1, "h-a"), (2, "h-b")),
                    replicas=(ReplicaView(1, "h-a", 5, True),),
                    leader_replica_id=1,
                    leader_host="h-a",
                ),
                ShardView(
                    shard_id=2,
                    members=((1, "h-b"),),
                    replicas=(),
                    leader_replica_id=0,
                    leader_host="",  # unknown leader: not in leader_map
                ),
            ),
        )
        assert view.leader_map() == {1: "h-a"}
        rc.refresh_from_view(view)
        assert rc.lookup(1) == "h-a" and rc.lookup(2) is None

    def test_event_fanout_add_tap_sees_leader_updated(self):
        seen = []
        fan = EventFanout()
        try:
            fan.add_tap(lambda name, args: seen.append((name, args)))
            info = LeaderInfo(4, replica_id=1, term=1, leader_id=1)
            fan.leader_updated(info)
            assert seen == [("leader_updated", (info,))]
        finally:
            fan.close()


# ---------------------------------------------------------------------------
# raft-level lease semantics (quorum-responded renewal, decay, loss)
# ---------------------------------------------------------------------------
class TestRaftLease:
    def _leader(self, check_quorum=True):
        from dragonboat_tpu.pb import Message, MessageType
        from raft_harness import Network

        net = Network.of(3, check_quorum=check_quorum)
        net.elect(1)
        return net, net.peers[1], Message, MessageType

    def test_lease_seeded_at_election_and_renewed_by_responses(self):
        net, l, Message, MessageType = self._leader()
        assert l.lease_remaining_ticks() > 0  # vote grants seed it
        # drive ticks WITH heartbeat exchange: lease never decays below
        # a full window minus the heartbeat cadence
        for _ in range(3 * l.election_timeout):
            net.submit(1, Message(type=MessageType.LOCAL_TICK))
        assert l.lease_remaining_ticks() >= l.election_timeout - 2

    def test_lease_decays_without_quorum_responses(self):
        net, l, Message, MessageType = self._leader()
        net.isolate(2)
        net.isolate(3)
        # responses stop arriving; the lease decays tick by tick (the
        # CHECK_QUORUM sweep will also depose the leader at the window
        # boundary, which forces remaining to 0 via the role gate)
        start = l.lease_remaining_ticks()
        for _ in range(l.election_timeout + 1):
            l.handle(Message(type=MessageType.LOCAL_TICK))
        assert l.lease_remaining_ticks() < max(start, 1), (
            start, l.lease_remaining_ticks(), l.role
        )
        assert l.lease_remaining_ticks() == 0

    def test_no_lease_without_check_quorum(self):
        net, l, Message, MessageType = self._leader(check_quorum=False)
        assert l.lease_remaining_ticks() == 0

    def test_follower_has_no_lease(self):
        net, l, Message, MessageType = self._leader()
        assert net.peers[2].lease_remaining_ticks() == 0

    def test_transfer_in_flight_zeroes_lease(self):
        # transfer votes bypass the vote-refusal lease (hint != 0), so
        # the target can be elected well inside the old window — the
        # lease must go to zero the moment a transfer is requested
        net, l, Message, MessageType = self._leader()
        assert l.lease_remaining_ticks() > 0
        l.handle(Message(type=MessageType.LEADER_TRANSFER, hint=2))
        assert l.leader_transfer_target == 2
        assert l.lease_remaining_ticks() == 0

    def test_boot_grace_refuses_votes_after_restart(self):
        from raft_harness import new_raft
        from dragonboat_tpu.pb import Message, MessageType, State

        # a voter restored from persisted state can't know how recently
        # it heard from a leader: it must refuse non-transfer votes for
        # one election window (leader_id is volatile — restart hole)
        r = new_raft(1, [1, 2, 3], check_quorum=True,
                     state=State(term=3, vote=2, commit=0))
        r.handle(Message(type=MessageType.REQUEST_VOTE, from_=2,
                         term=4, log_index=0, log_term=0))
        assert r.term == 3 and not r.msgs  # ignored inside boot grace
        for _ in range(r.election_timeout):
            r.tick_count += 1
        r.handle(Message(type=MessageType.REQUEST_VOTE, from_=2,
                         term=4, log_index=0, log_term=0))
        assert r.term == 4  # grace over: the vote request is processed
        # a fresh node (no persisted state) has no grace
        r2 = new_raft(1, [1, 2, 3], check_quorum=True)
        r2.handle(Message(type=MessageType.REQUEST_VOTE, from_=2,
                          term=4, log_index=0, log_term=0))
        assert r2.term == 4

    def test_single_voter_lease_always_held(self):
        from raft_harness import new_raft
        from dragonboat_tpu.pb import Message, MessageType

        r = new_raft(1, [1], check_quorum=True)
        r.handle(Message(type=MessageType.ELECTION))
        for _ in range(25):
            r.handle(Message(type=MessageType.LOCAL_TICK))
        assert r.lease_remaining_ticks() == r.election_timeout


# ---------------------------------------------------------------------------
# admission units
# ---------------------------------------------------------------------------
class TestAdmission:
    def _budget(self, p99=0.05):
        b = LatencyBudget(bootstrap=p99, floor=0.001)
        for _ in range(16):
            b.observe(p99)
        return b

    def test_queue_full_sheds_and_depth_accounting(self):
        m = MetricsRegistry()
        ac = AdmissionController(
            self._budget(), max_queue_per_shard=2, metrics=m
        )
        dl = time.monotonic() + 10.0
        assert ac.admit(1, dl) is None
        assert ac.admit(1, dl) is None
        assert ac.depth(1) == 2
        assert ac.admit(1, dl) == "queue_full"
        # another shard is unaffected (per-shard bound)
        assert ac.admit(2, dl) is None
        ac.complete(1)
        assert ac.admit(1, dl) is None
        assert ac.depth(1) == 2 and ac.depth(2) == 1
        assert ac.shed_total == 1
        assert m.counter("gateway_shed_total",
                         {"reason": "queue_full"}).value == 1

    def test_deadline_shed_when_p99_says_unreachable(self):
        ac = AdmissionController(self._budget(p99=0.5),
                                 max_queue_per_shard=8)
        # 50ms of headroom against a 500ms p99: cannot meet
        assert ac.admit(1, time.monotonic() + 0.05) == "deadline"
        # past deadline: shed without charging depth
        assert ac.admit(1, time.monotonic() - 1.0) == "deadline"
        assert ac.depth(1) == 0
        # ample headroom admits
        assert ac.admit(1, time.monotonic() + 5.0) is None

    def test_sustained_shed_fires_dump_once_per_cooldown(self):
        dumps = []
        ac = AdmissionController(
            self._budget(), max_queue_per_shard=1,
            dump_threshold=5, dump_window=5.0, dump_cooldown=60.0,
            dump_cb=dumps.append,
        )
        dl = time.monotonic() + 10.0
        assert ac.admit(1, dl) is None
        for _ in range(12):
            assert ac.admit(1, dl) == "queue_full"
        assert ac.dumps == 1 and len(dumps) == 1
        assert "sustained shedding" in dumps[0]


# ---------------------------------------------------------------------------
# cluster harness
# ---------------------------------------------------------------------------
GW_ADDRS = {1: "gwt-1", 2: "gwt-2", 3: "gwt-3"}


def make_gw_cluster(sm_factory=KVStore, *, check_quorum=True, shards=(1,),
                    rtt_ms=2, recorder=False, tag="gwt"):
    reset_inproc_network()
    addrs = {r: f"{tag}-{r}" for r in (1, 2, 3)}
    nhs = {}
    for r, a in addrs.items():
        d = f"/tmp/nh-{tag}-{r}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[a] = NodeHost(NodeHostConfig(
            nodehost_dir=d,
            rtt_millisecond=rtt_ms,
            raft_address=a,
            enable_flight_recorder=recorder,
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=2, apply_shards=2)
            ),
        ))
    for sid in shards:
        for r, a in addrs.items():
            nhs[a].start_replica(
                addrs, False, sm_factory,
                Config(replica_id=r, shard_id=sid, election_rtt=10,
                       heartbeat_rtt=1, check_quorum=check_quorum),
            )
    return addrs, nhs


def wait_leader(nhs, shard_id=1, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for a, nh in nhs.items():
            try:
                if nh.is_leader_of(shard_id):
                    return a
            except Exception:
                pass
        time.sleep(0.02)
    raise AssertionError(f"no leader for shard {shard_id} within {timeout}s")


def close_all(nhs, gw=None):
    if gw is not None:
        gw.close()
    for nh in nhs.values():
        try:
            nh.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# gateway end-to-end
# ---------------------------------------------------------------------------
class TestGatewayEndToEnd:
    def test_propose_read_and_routing_via_events(self):
        addrs, nhs = make_gw_cluster(tag="gwt-e2e")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            leader = wait_leader(nhs)
            h = gw.connect(1)
            for i in range(10):
                r = h.sync_propose(set_cmd(f"k{i}", i))
            assert r.value == 10
            # reads see the writes; the route learned from events or
            # discovery points at the leader host
            assert gw.read(1, "k9") == 9
            assert gw.routes.lookup(1) == leader
            st = gw.stats()
            assert st["route_table"].get(1) == leader
            assert st["committed"] == 10 and st["failed"] == 0
            assert st["lease_reads"] + st["read_fallbacks"] >= 1
            h.close()
        finally:
            close_all(nhs, gw)

    def test_per_session_ordering_under_async_submission(self):
        addrs, nhs = make_gw_cluster(AuditKV, tag="gwt-ord")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            wait_leader(nhs)
            h = gw.connect(1)
            futs = [
                h.propose(audit_set_cmd("seq", f"v{i}")) for i in range(24)
            ]
            for f in futs:
                f.result(20.0)
            # every replica applied the handle's writes in submission
            # order (the per-session in-flight gate + series discipline)
            deadline = time.time() + 10.0
            while time.time() < deadline:
                vals = [
                    [v for _, k, v in nh._get_node(1).sm.managed.sm.journal
                     if k == "seq"]
                    for nh in nhs.values()
                ]
                if all(len(v) == 24 for v in vals):
                    break
                time.sleep(0.05)
            for v in vals:
                assert v == [f"v{i}" for i in range(24)], v
            h.close()
        finally:
            close_all(nhs, gw)

    def test_noop_handle_and_closed_gateway_rejects(self):
        addrs, nhs = make_gw_cluster(tag="gwt-noop")
        gw = Gateway(nhs)
        try:
            wait_leader(nhs)
            h = gw.noop_handle(1)
            h.sync_propose(set_cmd("x", 1))
            assert gw.read(1, "x") == 1
            gw.close()
            with pytest.raises(GatewayClosed):
                h.propose(set_cmd("y", 2))
            with pytest.raises(GatewayClosed):
                gw.read(1, "x")
        finally:
            close_all(nhs, gw)


# ---------------------------------------------------------------------------
# lease reads
# ---------------------------------------------------------------------------
class TestLeaseReads:
    def test_lease_fast_path_skips_read_index(self):
        addrs, nhs = make_gw_cluster(tag="gwt-lease")
        gw = Gateway(nhs)
        try:
            leader = wait_leader(nhs)
            h = gw.connect(1)
            h.sync_propose(set_cmd("a", 1))
            # the leader host holds a CheckQuorum lease
            st = nhs[leader].lease_status(1)
            assert st["is_leader"] and st["check_quorum"]
            assert st["remaining_ticks"] > 0
            before = gw.stats()["lease_reads"]
            for _ in range(5):
                assert gw.read(1, "a") == 1
            assert gw.stats()["lease_reads"] >= before + 4
            # and the raw probe agrees
            ok, v = nhs[leader].try_lease_read(1, "a")
            assert ok and v == 1
            h.close()
        finally:
            close_all(nhs, gw)

    def test_no_lease_without_check_quorum_falls_back(self):
        addrs, nhs = make_gw_cluster(check_quorum=False, tag="gwt-nolease")
        gw = Gateway(nhs)
        try:
            leader = wait_leader(nhs)
            h = gw.noop_handle(1)
            h.sync_propose(set_cmd("a", 1))
            ok, _ = nhs[leader].try_lease_read(1, "a")
            assert not ok
            assert gw.read(1, "a") == 1  # ReadIndex fallback still serves
            assert gw.stats()["read_fallbacks"] >= 1
            assert gw.stats()["lease_reads"] == 0
        finally:
            close_all(nhs, gw)

    def test_leader_transfer_mid_lease_forces_fallback(self):
        addrs, nhs = make_gw_cluster(tag="gwt-xfer")
        gw = Gateway(nhs)
        try:
            leader = wait_leader(nhs)
            h = gw.noop_handle(1)
            h.sync_propose(set_cmd("a", 1))
            assert gw.read(1, "a") == 1
            old = nhs[leader]
            old_node = old._get_node(1)
            target = next(
                r for r, a in addrs.items() if a != leader
            )
            old.request_leader_transfer(1, target)
            # the OLD leader must lose the lease the moment it steps
            # down — no stale read past lease expiry
            deadline = time.time() + 10.0
            while time.time() < deadline:
                if not old.is_leader_of(1):
                    break
                time.sleep(0.01)
            assert not old.is_leader_of(1), "transfer did not complete"
            assert old_node.lease_remaining_ticks() == 0
            assert old.try_lease_read(1, "a") == (False, None)
            # gateway reads keep serving (rerouted / fallback)
            assert gw.read(1, "a") == 1
            new_leader = wait_leader(nhs)
            assert new_leader != leader
            # route converges to the new leader via leader_updated taps
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if gw.routes.lookup(1) == new_leader:
                    break
                time.sleep(0.02)
            assert gw.routes.lookup(1) == new_leader
        finally:
            close_all(nhs, gw)

    def test_leader_kill_mid_lease_forces_fallback(self):
        addrs, nhs = make_gw_cluster(tag="gwt-kill")
        gw = Gateway(nhs)
        try:
            leader = wait_leader(nhs)
            h = gw.noop_handle(1)
            h.sync_propose(set_cmd("a", 1))
            assert gw.read(1, "a") == 1
            victim = nhs[leader]
            victim_node = victim._get_node(1)
            assert victim_node.lease_held(0)
            # kill the leader host mid-lease: its replica stops, the
            # lease probe must refuse instantly (stopped gate), and the
            # survivors elect a new leader the gateway reroutes to
            gw.remove_host(leader)
            victim.close()
            assert victim_node.lease_remaining_ticks() == 0
            survivors = {a: nh for a, nh in nhs.items() if a != leader}
            new_leader = wait_leader(survivors, timeout=30.0)
            assert gw.read(1, "a", timeout=10.0) == 1
            assert new_leader in survivors
        finally:
            close_all(nhs, gw)

    def test_stale_read_containment_under_leader_kill_churn(self):
        """The audit/ containment pass over a gateway read/write
        history: writes via exactly-once handles, reads via the lease
        fast path (recorded as 'stale'-kind ops, so the checker holds
        them to the containment contract: never a never-written,
        aborted, or future value), leader killed mid-run."""
        addrs, nhs = make_gw_cluster(AuditKV, tag="gwt-audit")
        gw = Gateway(nhs, GatewayConfig(default_timeout=8.0))
        rec = HistoryRecorder()
        try:
            leader = wait_leader(nhs)
            wc = rec.new_client()
            rc_ = rec.new_client()
            stop = threading.Event()
            seq = [0]

            def writer():
                h = gw.connect(1, timeout=10.0)
                while not stop.is_set():
                    seq[0] += 1
                    val = f"w-{seq[0]}"
                    op = rec.invoke(wc, "w", "k", val)
                    try:
                        h.sync_propose(audit_set_cmd("k", val))
                        rec.ok(op)
                    except Exception:
                        rec.ambiguous(op)  # may have committed
                    time.sleep(0.005)

            def reader():
                while not stop.is_set():
                    op = rec.invoke(rc_, "stale", "k")
                    try:
                        rec.ok(op, gw.read(1, "k", timeout=5.0))
                    except Exception:
                        rec.fail(op)
                    time.sleep(0.003)

            tw = threading.Thread(target=writer, daemon=True, name="gw-aud-w")
            tr = threading.Thread(target=reader, daemon=True, name="gw-aud-r")
            tw.start()
            tr.start()
            time.sleep(1.5)
            # leader kill mid-lease, mid-traffic
            gw.remove_host(leader)
            nhs[leader].close()
            survivors = {a: nh for a, nh in nhs.items() if a != leader}
            wait_leader(survivors, timeout=30.0)
            time.sleep(2.0)
            stop.set()
            tw.join(timeout=15)
            tr.join(timeout=15)
            ops = rec.ops()
            reads_ok = [o for o in ops if o.kind == "stale"
                        and o.status == "ok"]
            assert len(reads_ok) > 20, rec.counts()
            violations = check_stale_reads(ops)
            assert violations == [], "\n".join(
                v.describe() for v in violations
            )
            # the lease fast path actually carried reads in this run
            assert gw.stats()["lease_reads"] > 0
        finally:
            close_all(nhs, gw)


# ---------------------------------------------------------------------------
# overload shedding
# ---------------------------------------------------------------------------
class TestOverload:
    def test_flood_sheds_bounded_queue_and_dumps_recorder(self):
        addrs, nhs = make_gw_cluster(recorder=True, tag="gwt-shed")
        gw = Gateway(nhs, GatewayConfig(
            workers=1,
            max_queue_per_shard=8,
            shed_dump_threshold=10,
            shed_dump_window=5.0,
            shed_dump_cooldown=0.0,
            default_timeout=10.0,
        ))
        try:
            wait_leader(nhs)
            handles = [gw.noop_handle(1) for _ in range(16)]
            futs, sheds = [], 0
            for round_ in range(8):
                for i, h in enumerate(handles):
                    try:
                        futs.append(
                            h.propose(set_cmd(f"f{round_}-{i}", i))
                        )
                    except GatewayBusy:
                        sheds += 1
            # everything ADMITTED completes; everything else shed
            done = 0
            for f in futs:
                f.result(20.0)
                done += 1
            st = gw.stats()
            assert sheds > 0 and st["shed"] == sheds
            assert done == len(futs) and st["committed"] >= done
            # sustained shedding auto-dumped the flight recorder
            assert st["shed_dumps"] >= 1
            assert "sustained shedding" in gw.last_shed_dump
            # the shed landed in the flight recorder lane too
            ev = []
            for nh in nhs.values():
                if nh.recorder is not None:
                    ev.extend(nh.recorder.events(1))
            assert any(k == "gateway_shed" for _, _, _, k, _ in ev)
        finally:
            close_all(nhs, gw)

    def test_deadline_shed_rejects_before_queueing(self):
        addrs, nhs = make_gw_cluster(tag="gwt-dl")
        budget = LatencyBudget(bootstrap=2.0, floor=0.001)
        for _ in range(16):
            budget.observe(2.0)  # observed p99: 2s commits
        gw = Gateway(nhs, GatewayConfig(budget=budget))
        try:
            wait_leader(nhs)
            h = gw.noop_handle(1)
            with pytest.raises(GatewayBusy, match="deadline"):
                h.propose(set_cmd("x", 1), timeout=0.05)
            assert gw.stats()["shed"] == 1
            assert gw.admission.depth(1) == 0  # nothing charged
        finally:
            close_all(nhs, gw)


# ---------------------------------------------------------------------------
# the ReadIndex fallback: one attempt is not the whole deadline
# ---------------------------------------------------------------------------
class _LostReadHost:
    """A NodeHost stand-in for the read path alone: no lease, and a
    ``sync_read`` whose first ``lose`` requests are never answered (a
    ReadIndex request lost to a change of leader), so each of them
    waits its timeout out and fails, as ``NodeHost.sync_read`` does."""

    def __init__(self, lose: int):
        self.lose = lose
        self.timeouts = []
        self.forwards = []

    def is_leader_of(self, shard_id):
        return True

    def _get_node(self, shard_id):
        return object()

    def lease_read(self, shard_id, query, margin_ticks=2):
        from dragonboat_tpu.node import LEASE_MISS_UNREPORTED

        return LEASE_MISS_UNREPORTED, None

    def sync_read(self, shard_id, query, timeout=5.0, forward=True):
        from dragonboat_tpu.nodehost import TimeoutError_

        self.timeouts.append(timeout)
        self.forwards.append(forward)
        if len(self.timeouts) <= self.lose:
            time.sleep(timeout)
            raise TimeoutError_("TIMEOUT")
        return f"value-of-{query}"


class TestReadIndexFallbackPerTry:
    def test_a_lost_read_index_request_costs_one_try_not_the_deadline(self):
        budget = LatencyBudget(bootstrap=0.05, floor=0.05, election_window=0.1)
        per_try = budget.per_try_timeout()
        assert per_try == pytest.approx(0.2)
        host = _LostReadHost(lose=2)
        gw = Gateway({"h1": host}, GatewayConfig(budget=budget))
        try:
            t0 = time.monotonic()
            assert gw.read(7, "k", timeout=30.0) == "value-of-k"
            took = time.monotonic() - t0
        finally:
            gw.close()
        # two tries lost, the third answered: two per-try waits, not 30 s
        assert host.timeouts == [pytest.approx(per_try)] * 3
        # leader-or-nothing for one election window (0.1 s, over after
        # the first try), forwarded by whoever carries the group after
        assert host.forwards == [False, True, True]
        assert 2 * per_try <= took < 5.0
        assert gw.stats()["read_fallbacks"] == 1

    def test_the_deadline_still_bounds_the_last_try_and_the_read(self):
        from dragonboat_tpu.nodehost import TimeoutError_

        budget = LatencyBudget(bootstrap=0.05, floor=0.05, election_window=0.1)
        host = _LostReadHost(lose=10**6)
        gw = Gateway({"h1": host}, GatewayConfig(budget=budget))
        try:
            t0 = time.monotonic()
            with pytest.raises(TimeoutError_):
                gw.read(7, "k", timeout=0.5)
            took = time.monotonic() - t0
        finally:
            gw.close()
        assert 0.5 <= took < 2.0
        assert max(host.timeouts) <= 0.2 + 1e-6   # never over one try
        assert min(host.timeouts) > 0.0           # the last one: what was left


# ---------------------------------------------------------------------------
# snapshot-cap feedback auto-wiring (ROADMAP 5a)
# ---------------------------------------------------------------------------
class _CapFakeHost:
    """A NodeHost stand-in with only what the cap wiring touches: a
    transport carrying a shared snapshot pacer behind the
    ``set_snapshot_send_rate`` runtime knob.  No event fanout — the
    gateway tolerates tap failures (routes via discovery)."""

    class _T:
        def __init__(self, rate):
            from dragonboat_tpu.bigstate.pacing import TokenBucket

            self.max_snapshot_send_rate = rate or 0
            self.snapshot_pacer = TokenBucket(rate) if rate else None

        def set_snapshot_send_rate(self, rate):
            from dragonboat_tpu.bigstate.pacing import TokenBucket

            self.max_snapshot_send_rate = rate
            if rate > 0:
                if self.snapshot_pacer is None:
                    self.snapshot_pacer = TokenBucket(rate)
                else:
                    self.snapshot_pacer.set_rate(rate)
            else:
                self.snapshot_pacer = None

    def __init__(self, rate):
        self.transport = self._T(rate)

    def set_snapshot_send_rate(self, rate):
        self.transport.set_snapshot_send_rate(rate)


def _wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


class TestCapFeedbackWiring:
    def test_degraded_commit_latency_shrinks_the_cap(self):
        """A host with a configured stream cap, fronted by a gateway
        whose LatencyBudget observes degraded commits, gets its cap
        shrunk automatically; healthy latency recovers it (AIMD)."""
        budget = LatencyBudget(bootstrap=0.01, floor=0.001)
        host = _CapFakeHost(rate=1_000_000.0)
        gw = Gateway(
            {"h1": host},
            GatewayConfig(
                budget=budget,
                cap_feedback_target_p99=0.05,
                cap_feedback_interval=0.02,
            ),
        )
        try:
            pacer = host.transport.snapshot_pacer
            # the loop binds lazily from the feedback thread (the
            # runtime knob may configure caps long after attach)
            assert _wait_for(lambda: "h1" in gw.cap_feedback_stats())
            for _ in range(32):
                budget.observe(0.5)  # p99 way over the 50ms target
            assert _wait_for(lambda: pacer.rate < 1_000_000.0), (
                "cap never shrank"
            )
            st = gw.cap_feedback_stats()["h1"]
            assert st["adjustments"] >= 1 and st["base_rate"] == 1_000_000.0
            # healthy again: flush the degraded samples out of the
            # budget's sliding window so p99 actually drops, then the
            # loop recovers toward (and caps at) base
            for _ in range(600):
                budget.observe(0.001)
            low = pacer.rate
            assert _wait_for(lambda: pacer.rate > low), "cap never recovered"
        finally:
            gw.close()

    def test_close_restores_the_configured_cap(self):
        """A cap shrunk by the AIMD loop must not outlive the gateway
        at the floor: close() hands the host its configured base back
        (the host outlives the gateway; nothing else would grow it)."""
        budget = LatencyBudget(bootstrap=0.01, floor=0.001)
        host = _CapFakeHost(rate=1_000_000.0)
        gw = Gateway(
            {"h1": host},
            GatewayConfig(
                budget=budget, cap_feedback_target_p99=0.05,
                cap_feedback_interval=0.02,
            ),
        )
        try:
            pacer = host.transport.snapshot_pacer
            for _ in range(32):
                budget.observe(0.5)
            assert _wait_for(lambda: pacer.rate < 1_000_000.0)
        finally:
            gw.close()
        assert host.transport.snapshot_pacer.rate == 1_000_000.0

    def test_late_configured_cap_and_runtime_retune(self):
        """The runtime knob works END TO END: a cap configured AFTER
        attach gains a loop automatically, and raising the configured
        base moves the AIMD ceiling instead of being clamped back to
        the stale attach-time base (review findings)."""
        budget = LatencyBudget(bootstrap=0.01, floor=0.001)
        host = _CapFakeHost(rate=None)  # no cap at attach time
        gw = Gateway(
            {"h1": host},
            GatewayConfig(
                budget=budget, cap_feedback_target_p99=0.05,
                cap_feedback_interval=0.02,
            ),
        )
        try:
            assert gw.cap_feedback_stats() == {}
            host.set_snapshot_send_rate(1_000_000.0)  # operator knob
            assert _wait_for(lambda: "h1" in gw.cap_feedback_stats())
            # raise the configured base: the loop must track it, and
            # with healthy p99 the rate may grow PAST the old base
            host.set_snapshot_send_rate(2_000_000.0)
            assert _wait_for(
                lambda: gw.cap_feedback_stats().get("h1", {}).get(
                    "base_rate"
                ) == 2_000_000.0
            )
            # remove the cap: the loop retires instead of ticking an
            # orphaned bucket
            host.set_snapshot_send_rate(0)
            assert _wait_for(lambda: gw.cap_feedback_stats() == {})
        finally:
            gw.close()

    def test_opt_out_and_capless_hosts(self):
        """cap_feedback=False attaches no loop; a host without a
        configured cap (pacer None) never gets one invented for it."""
        host = _CapFakeHost(rate=8_000_000.0)
        gw = Gateway({"h1": host}, GatewayConfig(cap_feedback=False))
        try:
            assert gw.cap_feedback_stats() == {}
            assert gw._cap_thread is None
        finally:
            gw.close()
        capless = _CapFakeHost(rate=None)
        gw2 = Gateway({"h1": capless}, GatewayConfig())
        try:
            assert gw2.cap_feedback_stats() == {}
            assert capless.transport.snapshot_pacer is None
        finally:
            gw2.close()

    def test_remove_host_drops_its_loop(self):
        host = _CapFakeHost(rate=1_000_000.0)
        gw = Gateway({"h1": host}, GatewayConfig(cap_feedback_interval=0.05))
        try:
            assert _wait_for(lambda: "h1" in gw.cap_feedback_stats())
            gw.remove_host("h1")
            assert gw.cap_feedback_stats() == {}
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# the worker wakes on events (docs/GATEWAY.md "The worker")
# ---------------------------------------------------------------------------
class _ScriptedHost:
    """A NodeHost stand-in for the propose path alone: it leads every
    shard, records what it was asked in order, and answers each
    proposal as ``answer(shard_id, cmd)`` says -- ``"now"`` (notified
    COMPLETED before ``propose`` returns), ``"dropped"`` (notified
    DROPPED before it returns), ``"soon"`` (COMPLETED from another
    thread, 10 ms later) or ``"never"``.  ``gate``, when given, holds
    the proposal of ``b"plug"`` inside ``propose`` until it is set."""

    _closed = False

    def __init__(self, answer=lambda shard_id, cmd: "now", gate=None):
        self.answer = answer
        self.gate = gate
        self.asked = []   # (shard_id, cmd) in the order proposed
        self.states = []

    def is_leader_of(self, shard_id):
        return True

    def _get_node(self, shard_id):
        return object()

    def add_event_tap(self, tap):
        pass

    def remove_event_tap(self, tap):
        pass

    def propose(self, session, cmd, timeout, parent=None, forward=True):
        from dragonboat_tpu.request import RequestResultCode, RequestState

        if self.gate is not None and cmd == b"plug":
            assert self.gate.wait(10.0)
        self.asked.append((session.shard_id, cmd))
        rs = RequestState(len(self.asked), 0)
        self.states.append(rs)
        how = self.answer(session.shard_id, cmd)
        if how == "now":
            rs.notify(RequestResultCode.COMPLETED)
        elif how == "dropped":
            rs.notify(RequestResultCode.DROPPED)
        elif how == "soon":
            threading.Timer(
                0.01, rs.notify, (RequestResultCode.COMPLETED,)).start()
        return rs


def _scripted_gw(host, **config):
    config.setdefault("cap_feedback", False)
    return Gateway({"h1": host}, GatewayConfig(**config))


def _wakes_few_checks_no_timed_wake():
    shards = (1, 2, 3, 4, 5)
    addrs, nhs = make_gw_cluster(shards=shards, tag="gwt-wake")
    gw = Gateway(nhs, GatewayConfig(workers=2))
    try:
        handles = []
        for sid in shards:
            wait_leader(nhs, sid)
            handles.append(gw.noop_handle(sid))
            handles[-1].sync_propose(set_cmd("warm", 0), 20.0)  # the route
        s0 = gw.stats()
        futs = [handles[i % len(handles)].propose(set_cmd(f"k{i}", i), 20.0)
                for i in range(60)]
        for f in futs:
            f.result(20.0)
        s1 = gw.stats()
        d = {k: s1[k] - s0[k] for k in (
            "committed", "poll_checks", "poll_passes", "wakes",
            "wakes_timed", "t_worker_cpu_ms")}
        assert d["committed"] == 60
        # one check a notified pair, and one more only after a DROPPED
        assert d["committed"] <= d["poll_checks"] <= 2 * d["committed"], d
        assert 0 < d["poll_passes"] <= d["poll_checks"]
        assert d["wakes_timed"] == 0, d
        # a wake brings a submission or a completion: never more wakes
        # than those, with one to spare for a set() that landed late
        assert 0 < d["wakes"] <= 2 * d["committed"] + 2, d
        assert d["t_worker_cpu_ms"] > 0.0
    finally:
        close_all(nhs, gw)


def _notify_before_arm_is_not_lost():
    host = _ScriptedHost()  # every RequestState comes back notified
    gw = _scripted_gw(host, workers=2)
    try:
        futs = [gw.noop_handle(sid).propose(b"c%d" % sid, 5.0)
                for sid in range(1, 9)]
        for f in futs:
            f.result(5.0)
        st = gw.stats()
        assert st["committed"] == 8 and st["failed"] == 0
        assert st["poll_checks"] == 8 and st["wakes_timed"] == 0
        # armed all the same: a later notify would have found a waker
        assert all(rs.waker is not None for rs in host.states)
    finally:
        gw.close()


def _never_notified_times_out_by_the_clock_and_blocks_nobody():
    from dragonboat_tpu.nodehost import TimeoutError_

    # shards 2 and 4 are the same worker's (of two); 2 lost its quorum
    host = _ScriptedHost(
        lambda sid, cmd: {2: "never", 4: "soon"}.get(sid, "now"))
    gw = _scripted_gw(host, workers=2)
    try:
        t0 = time.monotonic()
        lost = gw.noop_handle(2).propose(b"lost", 0.4)
        h4, h6 = gw.noop_handle(4), gw.noop_handle(6)
        for i in range(5):
            h4.sync_propose(b"live%d" % i, 5.0)
        for i in range(300):
            h6.sync_propose(b"more%d" % i, 5.0)
        assert time.monotonic() - t0 < 0.35 and not lost.done()
        # the answered ones do not queue up behind the lost one's
        # deadline for the length of it
        assert len(gw._wstate[0].expiry) <= 2 * 1 + 64 + 1
        with pytest.raises(TimeoutError_):
            lost.result(5.0)
        assert 0.4 <= lost.t_done - t0 < 0.5
        st = gw.stats()
        assert st["committed"] == 305 and st["failed"] == 1
        # the lost pair was looked at once, when its deadline came
        assert st["poll_checks"] == 306 and st["wakes_timed"] == 1, st
    finally:
        gw.close()


def _a_leaderless_shard_is_asked_again_at_a_pause_that_doubles():
    from dragonboat_tpu.nodehost import RequestDropped

    host = _ScriptedHost(lambda sid, cmd: "dropped" if sid == 2 else "soon")
    gw = _scripted_gw(host, workers=1)
    try:
        t0 = time.monotonic()
        lost = gw.noop_handle(2).propose(b"lost", 0.3)
        gw.noop_handle(4).sync_propose(b"live", 5.0)
        assert time.monotonic() - t0 < 0.25
        with pytest.raises(RequestDropped):
            lost.result(5.0)
        # at once, then after 1, 2, 4, 8, 16, 32, 32, ... ms: ~14 in
        # 0.3 s, where asking as fast as it answers would be thousands
        asked = sum(1 for sid, _ in host.asked if sid == 2)
        assert 6 <= asked <= 20, asked
        st = gw.stats()
        assert st["reroutes"] == asked - 1 and st["proposed"] == asked + 1
        assert st["wakes_timed"] <= asked
    finally:
        gw.close()


def _arm_against_notify_loses_nothing_and_checks_nothing_twice():
    import queue
    import sys

    # the notify of every proposal races the worker's arm of it: four
    # threads complete what the host was just asked, while the worker
    # is between ``nh.propose`` returning and ``rs.waker`` being set
    asked = queue.SimpleQueue()
    host = _ScriptedHost(lambda sid, cmd: "never")
    propose = host.propose

    def racing_propose(*a, **kw):
        rs = propose(*a, **kw)
        asked.put(rs)
        return rs

    def notifier():
        from dragonboat_tpu.request import RequestResultCode

        while True:
            rs = asked.get()
            if rs is None:
                return
            rs.notify(RequestResultCode.COMPLETED)

    host.propose = racing_propose
    notifiers = [threading.Thread(target=notifier, daemon=True)
                 for _ in range(4)]
    gw = _scripted_gw(host, workers=2)
    clients, each, errors = 16, 150, []

    def client(i):
        h = gw.noop_handle(1 + i % 8)
        try:
            for n in range(each):
                h.sync_propose(b"%d-%d" % (i, n), 20.0)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in notifiers:
            t.start()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        for _ in notifiers:
            asked.put(None)
        gw.close()
    assert not errors, errors[:3]
    st = gw.stats()
    assert st["committed"] == clients * each and st["failed"] == 0
    # a pair reported twice (by its waker and by the look after the
    # arm) is checked once
    assert st["poll_checks"] == clients * each
    assert st["wakes_timed"] == 0


def _idle_lanes_cost_no_wake():
    host = _ScriptedHost()
    gw = _scripted_gw(host, workers=4)
    try:
        futs = [gw.noop_handle(sid).propose(b"x", 10.0)
                for sid in range(1, 1001)]
        for f in futs:
            f.result(10.0)
        assert len(gw._lanes) == 1000
        assert _wait_for(lambda: not any(w.pending for w in gw._wstate))
        time.sleep(0.05)  # a last set() that landed late
        s0 = gw.stats()
        time.sleep(0.3)
        s1 = gw.stats()
        for k in ("wakes", "wakes_timed", "poll_passes", "t_worker_cpu_ms"):
            assert s1[k] == s0[k], k
        # and one more operation is one wake or two, not a scan
        gw.noop_handle(7).sync_propose(b"y", 5.0)
        assert 1 <= gw.stats()["wakes"] - s1["wakes"] <= 2
    finally:
        gw.close()


def _fifo_in_a_shard_and_max_batch_a_pass():
    gate = threading.Event()
    host = _ScriptedHost(gate=gate)
    gw = _scripted_gw(host, workers=1, max_batch=4)
    try:
        # the worker sits inside propose(plug) while both lanes fill up
        futs = [gw.noop_handle(1).propose(b"plug", 10.0)]
        assert _wait_for(lambda: not gw._lanes.get(1, True))
        for sid in (1, 2):
            futs += [gw.noop_handle(sid).propose(b"%d-%02d" % (sid, i), 10.0)
                     for i in range(10)]
        gate.set()
        for f in futs:
            f.result(10.0)
        turns = [(1, 0, 4), (2, 0, 4), (1, 4, 8), (2, 4, 8),
                 (1, 8, 10), (2, 8, 10)]
        assert host.asked == [(1, b"plug")] + [
            (sid, b"%d-%02d" % (sid, i))
            for sid, lo, hi in turns for i in range(lo, hi)]
    finally:
        gate.set()
        gw.close()


def _close_seals_the_queued_and_marks_the_pending_ambiguous():
    from dragonboat_tpu.client import Session
    from dragonboat_tpu.gateway import ClientHandle

    gate = threading.Event()
    host = _ScriptedHost(lambda sid, cmd: "never", gate=gate)
    gw = _scripted_gw(host, workers=1)
    once = Session.new_session(3)
    once.prepare_for_propose()
    series = once.series_id
    h_once, h_noop = ClientHandle(gw, once), gw.noop_handle(1)
    pend = [h_once.propose(b"p-once", 10.0), h_noop.propose(b"p-noop", 10.0)]
    assert _wait_for(lambda: len(gw._wstate[0].pending) == 2)
    noop_req = next(r for r in gw._wstate[0].pending if r.handle is h_noop)
    behind = h_noop.propose(b"behind-its-handle", 10.0)
    plug = gw.noop_handle(5).propose(b"plug", 10.0)  # holds the worker
    assert _wait_for(lambda: not gw._lanes.get(5, True))
    in_lane = gw.noop_handle(7).propose(b"in-its-lane", 10.0)
    closer = threading.Thread(target=gw.close)
    closer.start()
    assert _wait_for(lambda: gw._closed)
    gate.set()
    closer.join(10.0)
    assert not closer.is_alive()
    for f in pend + [behind, plug, in_lane]:
        with pytest.raises(GatewayClosed):
            f.result(5.0)
    # neither queued request was ever proposed
    assert sorted(c for _, c in host.asked) == [b"p-noop", b"p-once", b"plug"]
    # the pending ones may still commit: the exactly-once series is
    # burned, the at-most-once request stays marked
    assert once.series_id == series + 1
    assert noop_req.ambiguous
    with pytest.raises(GatewayClosed):
        h_noop.propose(b"late", 1.0)


@pytest.mark.parametrize("scenario", [
    _wakes_few_checks_no_timed_wake,
    _notify_before_arm_is_not_lost,
    _never_notified_times_out_by_the_clock_and_blocks_nobody,
    _a_leaderless_shard_is_asked_again_at_a_pause_that_doubles,
    _arm_against_notify_loses_nothing_and_checks_nothing_twice,
    _idle_lanes_cost_no_wake,
    _fifo_in_a_shard_and_max_batch_a_pass,
    _close_seals_the_queued_and_marks_the_pending_ambiguous,
], ids=lambda fn: fn.__name__.strip("_"))
def test_worker_wakes_on_events(scenario):
    scenario()
