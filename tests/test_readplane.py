"""Read plane (dragonboat_tpu.readplane, docs/READPLANE.md).

Covers the follower-read safety edges the subsystem's contract hangs
on:

* protocol level (deterministic raft harness): the follower's
  forwarded-ReadIndex ledger fails fast on every leadership-change
  signal — term-bump reset, pre-vote candidacy, and a leader SWITCH
  observed without a local term bump — and the heartbeat's uncapped
  commit advisory (``leader_commit_hint``) tracks the leader's real
  commit even when the capped per-follower commit understates it;
* end to end (3-host in-proc cluster behind the gateway): one read
  per consistency level with its provenance stamp and per-path
  counters; leader TRANSFER then follower reads never serve
  pre-transfer state as linearizable; leader KILL mid-storm keeps
  follower-linearizable reads monotonic (once the post-kill value is
  observed, the pre-kill value never reappears); a membership change
  removing the serving follower re-routes reads to the survivors;
* a partitioned follower (quorum lost) sheds BOUNDED_STALENESS reads
  once the bound decays, and refuses follower-linearizable reads
  outright;
* version skew: a pre-readplane server answers the consistency byte
  with "unknown read mode" — the client raises ReadUnsupported and the
  gateway degrades to a leader read, preserving the contract;
* ReadRouter units: power-of-two-choices prefers the lower observed
  p99 and penalties bias selection away from a dark replica.
"""
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
from dragonboat_tpu.audit import run_audit
from dragonboat_tpu.audit.history import (
    AuditClient,
    HistoryRecorder,
    run_workload,
)
from dragonboat_tpu.audit.model import AuditKV, audit_set_cmd
from dragonboat_tpu.pb import Message, MessageType
from dragonboat_tpu.raft.raft import RaftRole
from dragonboat_tpu.readplane import (
    Consistency,
    ReadResult,
    ReadRouter,
    ReadUnsupported,
    StaleBoundExceeded,
)
from dragonboat_tpu.transport.inproc import reset_inproc_network
from dragonboat_tpu.transport.wire import RPC_ERR, RpcResponse

from raft_harness import Network
from test_gateway import close_all, make_gw_cluster, wait_leader
from test_nodehost import KVStore, set_cmd


# ---------------------------------------------------------------------------
# protocol level: the leadership-change abort + the commit advisory
# ---------------------------------------------------------------------------
class TestForwardedReadAbort:
    def _forward_unanswered(self, net, follower=2):
        """Forward a ReadIndex from ``follower`` with the RESP leg
        dropped: the confirmation round stays in flight, ledgered."""
        net.drop_types.add(MessageType.READ_INDEX_RESP)
        net.submit(
            follower,
            Message(type=MessageType.READ_INDEX, hint=7, hint_high=8),
        )
        f = net.peers[follower]
        assert (7, 8) in f.forwarded_reads
        assert not f.drain_ready_to_reads()
        return f

    def test_resp_clears_ledger_and_serves(self):
        net = Network.of(3)
        net.elect(1)
        net.propose(1, b"x")
        net.submit(
            2, Message(type=MessageType.READ_INDEX, hint=1, hint_high=2)
        )
        f = net.peers[2]
        # the RESP arrived: ledger empty, the read is ready locally
        assert f.forwarded_reads == {}
        rtr = f.drain_ready_to_reads()
        assert len(rtr) == 1
        assert rtr[0].index == net.peers[1].log.committed

    def test_term_bump_new_leader_aborts_forwarded_round(self):
        net = Network.of(3)
        net.elect(1)
        net.propose(1, b"x")
        f = self._forward_unanswered(net, follower=2)
        net.drop_types.clear()
        net.elect(3)  # term bump reaches 2 -> _reset -> abort
        assert f.forwarded_reads == {}
        _, dropped = f.drain_dropped()
        assert any((c.low, c.high) == (7, 8) for c in dropped)

    def test_own_prevote_candidacy_aborts_forwarded_round(self):
        net = Network.of(3, pre_vote=True)
        net.elect(1)
        net.propose(1, b"x")
        f = self._forward_unanswered(net, follower=2)
        # leader falls silent for this follower: election timeout makes
        # it a PRE-candidate — prevote skips _reset, but the "leader
        # may be gone" signal must still abort the in-flight round
        net.isolate(2)
        for _ in range(3 * f.randomized_election_timeout):
            f.handle(Message(type=MessageType.LOCAL_TICK))
            f.drain_messages()
            if f.role == RaftRole.PRE_CANDIDATE:
                break
        assert f.role == RaftRole.PRE_CANDIDATE
        assert f.forwarded_reads == {}
        _, dropped = f.drain_dropped()
        assert any((c.low, c.high) == (7, 8) for c in dropped)

    def test_leader_switch_without_term_bump_aborts(self):
        net = Network.of(3)
        net.elect(1)
        net.propose(1, b"x")
        f = self._forward_unanswered(net, follower=2)
        # a heartbeat from a DIFFERENT leader at the same local term
        # (this replica missed the election entirely): the old leader's
        # answer may predate the new leader's commits — abort
        f.handle(Message(type=MessageType.HEARTBEAT, from_=3, to=2,
                         term=f.term))
        assert f.leader_id == 3
        assert f.forwarded_reads == {}
        _, dropped = f.drain_dropped()
        assert any((c.low, c.high) == (7, 8) for c in dropped)

    def test_ledger_soft_cap_sheds_oldest(self):
        net = Network.of(3)
        net.elect(1)
        net.propose(1, b"x")
        f = net.peers[2]
        net.drop_types.add(MessageType.READ_INDEX_RESP)
        for i in range(4097):
            net.submit(
                2,
                Message(type=MessageType.READ_INDEX,
                        hint=100 + i, hint_high=0),
            )
        assert len(f.forwarded_reads) == 4097 - 1024
        _, dropped = f.drain_dropped()
        assert len(dropped) == 1024  # oldest shed as failed, not leaked
        assert dropped[0].low == 100


class TestLeaderCommitHint:
    def test_hint_tracks_leader_commit(self):
        net = Network.of(3)
        net.elect(1)
        net.propose(1, b"a")
        net.propose(1, b"b")
        lead = net.peers[1]
        for fid in (2, 3):
            assert net.peers[fid].leader_commit_hint == lead.log.committed

    def _commit_past_replica_3(self, net):
        """Commit entries via the 1+2 quorum while replica 3 misses
        them, then let heartbeats (but NOT the catch-up REPLICATE) flow
        to 3 again: its capped per-follower commit understates, the
        log_index advisory carries the leader's real commit."""
        net.cut(1, 3)
        net.propose(1, b"a")
        net.propose(1, b"b")
        assert net.peers[1].log.committed > net.peers[3].log.committed
        net.recover()
        net.drop_types.add(MessageType.REPLICATE)  # no catch-up
        net.tick_all(net.peers[1].heartbeat_timeout)

    def test_uncapped_advisory_outruns_capped_commit(self):
        net = Network.of(3)
        net.elect(1)
        self._commit_past_replica_3(net)
        lead, behind = net.peers[1], net.peers[3]
        assert behind.leader_commit_hint == lead.log.committed
        assert behind.leader_commit_hint > behind.log.committed

    def test_reset_floors_hint_to_local_commit(self):
        net = Network.of(3)
        net.elect(1)
        self._commit_past_replica_3(net)
        behind = net.peers[3]
        assert behind.leader_commit_hint > behind.log.committed
        # term bump from a NEW election (2's log is complete, so it can
        # win; REPLICATE stays dropped so 3 stays behind): _reset must
        # floor the dead leader's advisory back to the local commit —
        # a bounded probe must not trust a hint nobody backs anymore
        net.elect(2)
        assert behind.leader_commit_hint == behind.log.committed


# ---------------------------------------------------------------------------
# end to end: consistency levels through the gateway
# ---------------------------------------------------------------------------
def _read_path_totals(nhs):
    """Host-side served-path counters summed over the cluster."""
    tot = {}
    for nh in nhs.values():
        for k, v in nh.read_path_counts().items():
            tot[k] = tot.get(k, 0) + v
    return tot


class TestReadPlaneEndToEnd:
    def test_read_at_levels_stamps_and_counters(self):
        addrs, nhs = make_gw_cluster(tag="rp-lvl")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            leader = wait_leader(nhs)
            h = gw.connect(1)
            h.sync_propose(set_cmd("k", "v1"))
            h.close()

            res = gw.read_at(1, "k")
            assert isinstance(res, ReadResult)
            assert res.value == "v1"
            assert res.path in ("lease", "read_index")
            assert res.staleness_ticks == 0

            # follower-linearizable: confirmed via the leader's round,
            # served from a LOCAL state machine, stamped with applied
            deadline = time.time() + 20
            while True:
                resf = gw.read_at(
                    1, "k",
                    consistency=Consistency.FOLLOWER_LINEARIZABLE,
                )
                assert resf.value == "v1"
                assert resf.path == "follower"
                assert resf.applied_index >= 1
                if resf.host and resf.host != leader:
                    break  # p2c picked an actual follower at least once
                assert time.time() < deadline, "never served by follower"

            # bounded staleness: immediate local serve, stamped
            deadline = time.time() + 20
            while True:
                try:
                    resb = gw.read_at(
                        1, "k",
                        consistency=Consistency.BOUNDED_STALENESS,
                        bound_ticks=200,
                    )
                    break
                except StaleBoundExceeded:
                    assert time.time() < deadline
                    time.sleep(0.05)
            assert resb.value == "v1"
            assert resb.path == "bounded"
            assert resb.staleness_ticks <= 200

            st = gw.stats()
            rp = st["read_paths"]
            assert rp["follower"] >= 1 and rp["bounded"] >= 1
            assert rp["lease"] + rp["read_index"] >= 1
            assert st["replica_table"][1], "replica set never learned"
            # host-side counters mirror the served paths
            tot = _read_path_totals(nhs)
            assert tot["follower"] >= 1 and tot["bounded"] >= 1
        finally:
            close_all(nhs, gw)

    def test_recorded_mix_over_every_level_audits_green(self):
        """A short recorded read/write mix over all three levels: the
        Wing-Gong pass covers leader AND follower reads, the bounded
        pass checks every staleness stamp."""
        addrs, nhs = make_gw_cluster(AuditKV, tag="rp-mix")
        try:
            wait_leader(nhs)
            rec = HistoryRecorder()
            stop = threading.Event()
            clients = [AuditClient(nhs, 1, rec, seed=i) for i in (2, 3)]
            threads = run_workload(
                clients, ["k", "k2"], stop, read_ratio=0.25,
                stale_ratio=0.05, follower_ratio=0.2, bounded_ratio=0.2,
                pace=0.001,
            )
            time.sleep(1.2)
            stop.set()
            for t in threads:
                t.join(10.0)
            ops = rec.ops()
            assert {"r", "w"} <= {o.kind for o in ops}
            rep = run_audit(ops)
            assert rep.ok, rep.describe()
            tot = _read_path_totals(nhs)
            assert tot["follower"] >= 1 and tot["bounded"] >= 1, tot
        finally:
            close_all(nhs)

    def test_leader_transfer_never_serves_pre_transfer_state(self):
        addrs, nhs = make_gw_cluster(tag="rp-xfer")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            leader = wait_leader(nhs)
            h = gw.connect(1)
            h.sync_propose(set_cmd("k", "old"))
            old_nh = nhs[leader]
            target = next(
                r for r, a in addrs.items() if a != leader
            )
            old_nh.request_leader_transfer(1, target)
            deadline = time.time() + 20
            while nhs[leader].is_leader_of(1):
                assert time.time() < deadline, "transfer did not complete"
                time.sleep(0.02)
            wait_leader(nhs)
            h.sync_propose(set_cmd("k", "new"))
            h.close()
            # every follower-linearizable read after the post-transfer
            # ack MUST see the new value: a confirmation obtained from
            # the deposed leader would serve "old" — the abort protocol
            # (drop_pending_read_indexes) forbids exactly that
            for _ in range(10):
                res = gw.read_at(
                    1, "k",
                    consistency=Consistency.FOLLOWER_LINEARIZABLE,
                    timeout=10.0,
                )
                assert res.value == "new", res
        finally:
            close_all(nhs, gw)

    def test_leader_kill_mid_storm_follower_reads_stay_monotonic(self):
        addrs, nhs = make_gw_cluster(tag="rp-kill")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            leader = wait_leader(nhs)
            h = gw.connect(1)
            h.sync_propose(set_cmd("k", 1))
            h.close()
            stop = threading.Event()
            seen = [[] for _ in range(2)]  # per-thread completion order
            errors = []

            def storm(idx):
                while not stop.is_set():
                    try:
                        res = gw.read_at(
                            1, "k",
                            consistency=Consistency.FOLLOWER_LINEARIZABLE,
                            timeout=5.0,
                        )
                        seen[idx].append(res.value)
                    except Exception as e:  # noqa: BLE001 — a failed
                        # read is always allowed; a STALE one is not
                        errors.append(type(e).__name__)
                        time.sleep(0.02)

            threads = [
                threading.Thread(target=storm, args=(i,), daemon=True,
                                 name=f"rp-storm-{i}")
                for i in range(2)
            ]
            for t in threads:
                t.start()
            time.sleep(0.3)
            # KILL the leader host mid-round: in-flight confirmation
            # rounds against it must fail fast, never resolve stale
            nhs[leader].close()
            survivors = {a: nh for a, nh in nhs.items() if a != leader}
            new_leader = wait_leader(survivors)
            nh2 = survivors[new_leader]
            sess = nh2.get_noop_session(1)
            deadline = time.time() + 20
            while True:
                try:
                    nh2.sync_propose(sess, set_cmd("k", 2), timeout=5.0)
                    break
                except Exception:  # noqa: BLE001 — re-electing
                    assert time.time() < deadline
                    time.sleep(0.05)
            # every read INVOKED after the post-kill ack must see it —
            # that is the linearizability claim, with no concurrent-op
            # ambiguity (these reads are sequential in this thread)
            for _ in range(10):
                res = gw.read_at(
                    1, "k",
                    consistency=Consistency.FOLLOWER_LINEARIZABLE,
                    timeout=10.0,
                )
                assert res.value == 2, (
                    f"read after post-kill ack served stale state: {res}")
            stop.set()
            for t in threads:
                t.join(10.0)
            # per-thread monotonicity: a thread's reads are sequential,
            # so once it observes the post-kill value it must never
            # regress to the pre-kill one (a deposed leader's answer)
            for vals in seen:
                if 2 in vals:
                    tail = vals[vals.index(2):]
                    assert set(tail) == {2}, (
                        f"follower reads regressed: {tail[:20]}")
        finally:
            close_all(nhs, gw)

    def test_membership_change_removes_serving_follower(self):
        addrs, nhs = make_gw_cluster(tag="rp-mem")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            leader = wait_leader(nhs)
            h = gw.connect(1)
            h.sync_propose(set_cmd("k", "v"))
            h.close()
            # prime the replica set, then REMOVE a serving follower
            assert len(gw.routes.resolve_replicas(1)) == 3
            victim_r, victim_a = next(
                (r, a) for r, a in addrs.items() if a != leader
            )
            nhs[leader].sync_request_delete_replica(1, victim_r,
                                                    timeout=10.0)
            try:
                nhs[victim_a].stop_replica(1, victim_r)
            except Exception:  # noqa: BLE001 — may have self-stopped
                pass
            gw.routes.invalidate_replicas(1)
            # reads keep working and are never served by the removed
            # replica (rediscovery drops it: its _get_node raises)
            for _ in range(8):
                res = gw.read_at(
                    1, "k",
                    consistency=Consistency.FOLLOWER_LINEARIZABLE,
                    timeout=10.0,
                )
                assert res.value == "v"
                assert res.host != victim_a, res
            assert victim_a not in gw.routes.resolve_replicas(1)
        finally:
            close_all(nhs, gw)


# ---------------------------------------------------------------------------
# partitioned follower: bounded reads shed once the bound decays
# ---------------------------------------------------------------------------
class TestBoundedShedOnPartition:
    def test_quorum_loss_sheds_bounded_and_refuses_follower_reads(self):
        reset_inproc_network()
        addrs = {1: "rp2-1", 2: "rp2-2"}
        nhs = {}
        for r, a in addrs.items():
            d = f"/tmp/nh-rp2-{r}"
            shutil.rmtree(d, ignore_errors=True)
            nhs[a] = NodeHost(NodeHostConfig(
                nodehost_dir=d, rtt_millisecond=2, raft_address=a,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=1)),
            ))
        for r, a in addrs.items():
            nhs[a].start_replica(
                addrs, False, KVStore,
                Config(replica_id=r, shard_id=1, election_rtt=10,
                       heartbeat_rtt=1, check_quorum=True),
            )
        try:
            leader = wait_leader(nhs)
            follower = next(a for a in addrs.values() if a != leader)
            sess = nhs[leader].get_noop_session(1)
            nhs[leader].sync_propose(sess, set_cmd("k", "v"), timeout=10.0)
            # healthy: the follower serves within the bound.  The value
            # may legitimately LAG right after the commit (the follower
            # serves its applied state until the next heartbeat's commit
            # advisory lands) — bounded staleness promises an honest
            # stamp, not instant freshness — so poll until it converges.
            deadline = time.time() + 20
            while True:
                try:
                    res = nhs[follower].bounded_read(1, "k",
                                                     bound_ticks=50)
                    if res.value == "v":
                        break
                except StaleBoundExceeded:
                    pass
                assert time.time() < deadline, "never served healthy"
                time.sleep(0.02)
            assert res.value == "v" and res.staleness_ticks <= 50
            # partition = the other replica of a 2-replica shard dies:
            # no quorum, no leader, the survivor's bound decays
            nhs[leader].close()
            deadline = time.time() + 20
            while True:
                try:
                    nhs[follower].bounded_read(1, "k", bound_ticks=3)
                except StaleBoundExceeded:
                    break  # shed: the contract held
                assert time.time() < deadline, (
                    "partitioned follower kept serving bounded reads")
                time.sleep(0.02)
            assert nhs[follower].read_path_counts()["bounded_shed"] >= 1
            # follower-linearizable needs the leader round: must FAIL,
            # not serve local state as linearizable
            with pytest.raises(Exception):
                nhs[follower].follower_read(1, "k", timeout=0.5)
        finally:
            close_all(nhs)


# ---------------------------------------------------------------------------
# version skew: pre-readplane servers degrade to leader reads
# ---------------------------------------------------------------------------
class TestVersionSkew:
    def test_old_rpc_server_raises_read_unsupported(self):
        from dragonboat_tpu.gateway.rpc import RemoteHostHandle, RpcServer
        from test_rpc import _single_host

        nh = _single_host("rp-skew")
        srv = RpcServer(nh, "127.0.0.1:0")
        orig = srv._handle_read

        def old_handle_read(q, timeout):
            # a pre-readplane server: flags 0..2 only, everything else
            # is "unknown read mode N" (the historical error string)
            if q.flags > 2:
                return RpcResponse(
                    req_id=q.req_id, code=RPC_ERR,
                    error=f"unknown read mode {q.flags}",
                )
            return orig(q, timeout)

        srv._handle_read = old_handle_read
        srv.start()
        h = RemoteHostHandle(srv.listen_address, rtt_millisecond=5)
        try:
            s = nh.get_noop_session(1)
            nh.sync_propose(s, audit_set_cmd("k", "v"), timeout=10.0)
            assert h.sync_read(1, "k", timeout=10.0) == "v"
            with pytest.raises(ReadUnsupported):
                h.follower_read(1, "k", timeout=5.0)
            with pytest.raises(ReadUnsupported):
                h.bounded_read(1, "k")
        finally:
            h.close()
            srv.close()
            nh.close()

    def test_gateway_degrades_unsupported_to_leader_read(self):
        addrs, nhs = make_gw_cluster(tag="rp-degrade")
        gw = Gateway(nhs, GatewayConfig(workers=2))
        try:
            wait_leader(nhs)
            h = gw.connect(1)
            h.sync_propose(set_cmd("k", "v"))
            h.close()

            def unsupported(*a, **kw):
                raise ReadUnsupported("unknown read mode 3")

            for nh in nhs.values():
                nh.follower_read = unsupported
                nh.bounded_read = unsupported
            res = gw.read_at(
                1, "k", consistency=Consistency.FOLLOWER_LINEARIZABLE
            )
            assert res.value == "v"
            assert res.path in ("lease", "read_index")
            res = gw.read_at(
                1, "k", consistency=Consistency.BOUNDED_STALENESS
            )
            assert res.value == "v"
            assert res.path in ("lease", "read_index")
        finally:
            close_all(nhs, gw)


# ---------------------------------------------------------------------------
# router units
# ---------------------------------------------------------------------------
class TestReadRouter:
    def test_pick_edge_cases(self):
        r = ReadRouter(seed=1)
        assert r.pick([]) is None
        assert r.pick(["a"]) == "a"
        assert r.pick(["a", "b"], exclude=["a"]) == "b"
        assert r.pick(["a"], exclude=["a"]) is None

    def test_two_choices_prefers_lower_p99(self):
        r = ReadRouter(seed=7)
        for _ in range(128):
            r.observe("slow", 0.5)
            r.observe("fast", 0.001)
        picks = [r.pick(["slow", "fast"]) for _ in range(100)]
        # with two candidates p2c compares both every time: the slow
        # replica must never win a coin flip
        assert set(picks) == {"fast"}

    def test_penalty_biases_away_from_dark_replica(self):
        r = ReadRouter(seed=3)
        for h in ("a", "b", "c"):
            for _ in range(64):
                r.observe(h, 0.002)
        for _ in range(64):
            r.penalize("b")
        picks = [r.pick(["a", "b", "c"]) for _ in range(300)]
        # p2c still samples "b" but it loses every comparison; only the
        # (b,b)-impossible two-distinct sampling keeps it at zero
        assert picks.count("b") == 0
        assert picks.count("a") > 0 and picks.count("c") > 0

    def test_snapshot_surfaces_observed_p99(self):
        r = ReadRouter()
        for _ in range(64):
            r.observe("h", 0.25)
        snap = r.snapshot()
        assert snap["h"] == pytest.approx(0.25)
