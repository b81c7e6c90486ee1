"""Networked RPC ingress (gateway/rpc.py; docs/GATEWAY.md "Networked
ingress").

Covers, per the cross-process tentpole:

* wire codec units: request/response/value/stats round-trips, newer
  version rejection, payload bounds, trailing-byte strictness;
* end-to-end over a live in-proc NodeHost: exactly-once session
  lifecycle, noop proposes, sync/stale/lease reads, leader surface and
  placement probes — all through RpcServer + RemoteHostHandle;
* degradation matrix regressions: per-request deadlines fire against a
  mute server, connection loss fails pending ops (sent at-most-once
  noop -> TIMEOUT, everything else -> DROPPED) without ever hanging,
  ingress shed maps to retryable DROPPED, and the breaker darkens an
  unreachable remote so admission sheds before queueing;
* RouteFeeder units: gossip liveness overrides an answering-but-dead
  host, collect failures invalidate routes, refresh merges leaders;
* a 3-host gateway-over-RPC fleet surviving a leader kill with routed
  traffic (the in-proc twin of the multi-process smoke);
* the REAL thing: ``run_rpc_smoke`` — 2 OS processes, commits over
  TCP, SIGKILL the leader's process, recovery inside the SLA — and the
  3-process mini production day behind ``DRAGONBOAT_MULTIPROC=1``.
"""
import os
import shutil
import socket
import struct
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
from dragonboat_tpu.audit.model import AuditKV, audit_set_cmd
from dragonboat_tpu.client import SERIES_ID_FIRST_PROPOSAL, Session
from dragonboat_tpu.gateway.rpc import (
    RemoteHostHandle,
    RouteFeeder,
    RpcServer,
)
from dragonboat_tpu.gateway.routing import RoutingCache
from dragonboat_tpu.nodehost import TimeoutError_
from dragonboat_tpu.pb import Membership
from dragonboat_tpu.request import (
    RequestError,
    RequestResultCode,
    ShardNotFound,
    SystemBusy,
)
from dragonboat_tpu.transport.inproc import reset_inproc_network
from dragonboat_tpu.transport.tcp import _read_frame, _write_frame
from dragonboat_tpu.transport.wire import (
    KIND_RPC_REQ,
    RPC_OP_PROPOSE,
    RPC_READ_LEASE,
    WireError,
    decode_rpc_request,
    decode_rpc_response,
    decode_rpc_stats,
    decode_rpc_value,
    encode_rpc_request,
    encode_rpc_response,
    encode_rpc_stats,
    encode_rpc_value,
    RpcRequest,
    RpcResponse,
)

TIMEOUT = int(RequestResultCode.TIMEOUT)
DROPPED = int(RequestResultCode.DROPPED)
COMPLETED = int(RequestResultCode.COMPLETED)


# ---------------------------------------------------------------------------
# codec units (no cluster)
# ---------------------------------------------------------------------------
class TestRpcCodecs:
    def test_request_roundtrip(self):
        q = RpcRequest(req_id=7, op=RPC_OP_PROPOSE, flags=RPC_READ_LEASE,
                       shard_id=9, client_id=11, series_id=13,
                       responded_to=12, timeout_ms=250, arg=3,
                       payload=b"cmd-bytes")
        d = decode_rpc_request(encode_rpc_request(q))
        for f in ("req_id", "op", "flags", "shard_id", "client_id",
                  "series_id", "responded_to", "timeout_ms", "arg",
                  "payload"):
            assert getattr(d, f) == getattr(q, f), f

    def test_request_newer_version_rejected(self):
        buf = bytearray(encode_rpc_request(RpcRequest(req_id=1)))
        struct.pack_into("<I", buf, 0, 99)
        with pytest.raises(WireError):
            decode_rpc_request(bytes(buf))

    def test_request_trailing_bytes_rejected(self):
        buf = encode_rpc_request(RpcRequest(req_id=1)) + b"x"
        with pytest.raises(WireError):
            decode_rpc_request(buf)

    def test_request_oversized_payload_rejected(self):
        q = RpcRequest(req_id=1, payload=b"x" * (8 * 1024 * 1024 + 1))
        with pytest.raises(WireError):
            encode_rpc_request(q)

    def test_response_roundtrip(self):
        r = RpcResponse(req_id=42, code=COMPLETED, value=77,
                        data=b"blob", error="nope")
        d = decode_rpc_response(encode_rpc_response(r))
        assert (d.req_id, d.code, d.value, d.data, d.error) == (
            42, COMPLETED, 77, b"blob", "nope")

    def test_value_codec_preserves_types(self):
        for v in (None, b"bytes", "text", 12345, -7, True, False,
                  [1, "a"], {"k": [None, 2]}):
            got = decode_rpc_value(encode_rpc_value(v))
            assert got == v and type(got) is type(v), v

    def test_stats_roundtrip(self):
        rows = [{
            "shard_id": 1, "replica_id": 2, "leader_id": 2, "term": 5,
            "applied": 9, "proposals": 3, "device": -1,
            "membership": Membership(config_change_id=4,
                                     addresses={1: "a", 2: "b"}),
        }]
        nhid, raft, drows, rp = decode_rpc_stats(
            encode_rpc_stats("nhid-x", "127.0.0.1:1", rows))
        assert (nhid, raft) == ("nhid-x", "127.0.0.1:1")
        r = drows[0]
        for k in ("shard_id", "replica_id", "leader_id", "term",
                  "applied", "proposals", "device"):
            assert r[k] == rows[0][k], k
        assert r["membership"].addresses == {1: "a", 2: "b"}
        # legacy payload (no trailing section) decodes to empty counts
        assert rp == {}
        # flag-gated read-path section roundtrips
        counts = {"lease": 3, "follower": 9, "bounded": 1}
        _, _, _, rp2 = decode_rpc_stats(
            encode_rpc_stats("nhid-x", "127.0.0.1:1", rows,
                             read_paths=counts))
        assert rp2 == counts


# ---------------------------------------------------------------------------
# end-to-end over a live in-proc host
# ---------------------------------------------------------------------------
def _single_host(tag, *, check_quorum=True):
    reset_inproc_network()
    d = f"/tmp/nh-{tag}"
    shutil.rmtree(d, ignore_errors=True)
    nh = NodeHost(NodeHostConfig(
        nodehost_dir=d, rtt_millisecond=5, raft_address=f"{tag}-1",
        expert=ExpertConfig(
            engine=EngineConfig(exec_shards=1, apply_shards=1)),
    ))
    nh.start_replica(
        {1: f"{tag}-1"}, False, AuditKV,
        Config(replica_id=1, shard_id=1, election_rtt=10,
               heartbeat_rtt=1, pre_vote=True, check_quorum=check_quorum),
    )
    deadline = time.time() + 10
    while not nh.is_leader_of(1):
        assert time.time() < deadline, "no leader"
        time.sleep(0.02)
    return nh


@pytest.fixture(scope="module")
def rpc_host():
    nh = _single_host("rpc-e2e")
    srv = RpcServer(nh, "127.0.0.1:0")
    srv.start()
    h = RemoteHostHandle(srv.listen_address, rtt_millisecond=5)
    yield nh, srv, h
    h.close()
    srv.close()
    nh.close()


class TestRpcEndToEnd:
    def test_exactly_once_session_lifecycle(self, rpc_host):
        _, _, h = rpc_host
        s = h.sync_get_session(1, timeout=10.0)
        assert s.client_id != 0
        assert s.series_id == SERIES_ID_FIRST_PROPOSAL
        for i in range(3):
            res = h.sync_propose(s, audit_set_cmd("k", f"v{i}"),
                                 timeout=10.0)
            s.proposal_completed()
            assert res.value >= 1
        assert h.sync_read(1, "k", timeout=10.0) == "v2"
        # a REPLAYED series must dedupe server-side, not re-apply
        replay = Session(shard_id=1, client_id=s.client_id,
                         series_id=s.series_id - 1,
                         responded_to=s.responded_to - 1)
        h.sync_propose(replay, audit_set_cmd("k", "vdup"), timeout=10.0)
        assert h.sync_read(1, "k", timeout=10.0) == "v2"
        h.sync_close_session(s, timeout=10.0)

    def test_noop_propose_and_reads(self, rpc_host):
        _, _, h = rpc_host
        s = h.get_noop_session(1)
        h.sync_propose(s, audit_set_cmd("nk", "nv"), timeout=10.0)
        assert h.sync_read(1, "nk", timeout=10.0) == "nv"
        assert h.stale_read(1, "nk") == "nv"
        # the lease path needs CheckQuorum heartbeats to establish
        deadline = time.time() + 10
        while time.time() < deadline:
            ok, val = h.try_lease_read(1, "nk")
            if ok:
                assert val == "nv"
                return
            time.sleep(0.05)
        raise AssertionError("lease never held")

    @pytest.mark.parametrize("forward", [True, False])
    def test_leader_or_nothing_rides_the_request(self, rpc_host, forward):
        """``forward=False`` (the gateway's proposals) reaches the
        serving host's ``NodeHost.propose`` through the request's flags
        byte; without it the host forwards as it always did."""
        nh, _, h = rpc_host
        seen = []
        real = nh.propose

        def spy(*a, **kw):
            seen.append(kw.get("forward"))
            return real(*a, **kw)

        nh.propose = spy
        try:
            rc = h.propose(h.get_noop_session(1),
                           audit_set_cmd("fk", f"fv{forward}"), 10.0,
                           forward=forward)
            assert rc.wait(10.0) == RequestResultCode.COMPLETED
        finally:
            del nh.propose
        assert seen == [forward]

    def test_leader_surface_and_placement(self, rpc_host):
        nh, _, h = rpc_host
        assert h.get_leader_id(1) == (1, True)
        assert h.is_leader_of(1)
        assert not h.is_leader_of(99)
        assert h.raft_address() == nh.raft_address()
        h._get_node(1)  # placement probe: present
        with pytest.raises(ShardNotFound):
            h._get_node(99)

    def test_ingress_shed_is_retryable_dropped(self, rpc_host):
        nh, _, _ = rpc_host
        srv = RpcServer(nh, "127.0.0.1:0", max_inflight=0)
        srv.start()
        h = RemoteHostHandle(srv.listen_address, rtt_millisecond=5)
        try:
            # shed at the ingress door NEVER reached a pending table:
            # the async rc reads DROPPED (dedupe-safe, the gateway
            # retries it elsewhere) while the sync wrapper surfaces the
            # deliberate SystemBusy
            rc = h.propose(h.get_noop_session(1), b"x", 5.0)
            assert rc.wait(5.0) == RequestResultCode.DROPPED
            with pytest.raises(SystemBusy):
                h.sync_propose(h.get_noop_session(1), b"x", timeout=5.0)
        finally:
            h.close()
            srv.close()


# ---------------------------------------------------------------------------
# degradation matrix (mute server, connection loss, breaker)
# ---------------------------------------------------------------------------
class _MuteServer:
    """Accepts RPC connections and reads frames but never replies —
    a stalled remote, from the client's point of view."""

    def __init__(self):
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(4)
        self.address = "127.0.0.1:%d" % self._lsock.getsockname()[1]
        self._conns = []
        self.seen = []
        self._stop = threading.Event()
        self._lsock.settimeout(0.1)
        self._t = threading.Thread(target=self._main, daemon=True,
                                   name="test-mute-server")
        self._t.start()

    def _main(self):
        while not self._stop.is_set():
            try:
                sock, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            self._conns.append(sock)
            threading.Thread(target=self._drain, args=(sock,),
                             daemon=True, name="test-mute-drain").start()

    def _drain(self, sock):
        try:
            while True:
                got = _read_frame(sock)
                if got is None:
                    return
                self.seen.append(got)
        except Exception:  # noqa: BLE001 — test server teardown
            pass

    def drop_conns(self):
        for s in self._conns:
            # shutdown first: close() alone would leave the drain
            # thread's blocked recv holding the socket open (no FIN)
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        self._conns = []

    def close(self):
        self._stop.set()
        self.drop_conns()
        self._lsock.close()


class TestRpcDegradation:
    def test_deadline_fires_against_mute_server(self):
        srv = _MuteServer()
        h = RemoteHostHandle(srv.address, rtt_millisecond=5)
        try:
            t0 = time.monotonic()
            with pytest.raises(TimeoutError_):
                h.sync_propose(h.get_noop_session(1), b"x", timeout=0.3)
            took = time.monotonic() - t0
            assert took < 2.0, f"deadline did not bound the wait: {took}"
            assert srv.seen and srv.seen[0][0] == KIND_RPC_REQ
        finally:
            h.close()
            srv.close()

    def test_connection_loss_fails_pending_not_hangs(self):
        srv = _MuteServer()
        h = RemoteHostHandle(srv.address, rtt_millisecond=5)
        try:
            # a SENT at-most-once (noop) proposal is maybe-committed:
            # connection loss must surface TIMEOUT, never DROPPED
            rc_noop = h.propose(h.get_noop_session(1), b"x", 5.0)
            # a SENT exactly-once proposal is dedupe-safe: DROPPED
            eo = Session(shard_id=1, client_id=77,
                         series_id=SERIES_ID_FIRST_PROPOSAL,
                         responded_to=0)
            rc_eo = h.propose(eo, b"y", 5.0)
            deadline = time.time() + 5
            while len(srv.seen) < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert len(srv.seen) >= 2, "requests never hit the wire"
            srv.drop_conns()
            assert rc_noop.wait(5.0) == RequestResultCode.TIMEOUT
            assert rc_eo.wait(5.0) == RequestResultCode.DROPPED
        finally:
            h.close()
            srv.close()

    def test_breaker_darkens_dead_remote(self):
        srv = _MuteServer()
        h = RemoteHostHandle(srv.address, rtt_millisecond=5,
                             connect_timeout=0.2)
        try:
            assert not h._closed
            srv.close()
            # repeated failures open the breaker; once dark, proposes
            # come back pre-completed DROPPED with no connect attempt
            for _ in range(8):
                rc = h.propose(h.get_noop_session(1), b"x", 1.0)
                rc.wait(2.0)
                if h._closed:
                    break
            assert h._closed, "breaker never darkened the remote"
            t0 = time.monotonic()
            rc = h.propose(h.get_noop_session(1), b"x", 1.0)
            assert rc.wait(0.5) == RequestResultCode.DROPPED
            assert time.monotonic() - t0 < 0.25, "dark path not fast"
        finally:
            h.close()


# ---------------------------------------------------------------------------
# RouteFeeder units (fake hosts, fake gossip — no cluster)
# ---------------------------------------------------------------------------
class _FakeHost:
    def __init__(self, nhid, replica_id, leader_id, members):
        self.nodehost_id = nhid
        self._closed = False
        self.fail_stats = False
        self._row = {
            "shard_id": 1, "replica_id": replica_id,
            "leader_id": leader_id, "term": 3, "applied": 10,
            "proposals": 0, "device": -1,
            "membership": Membership(config_change_id=0,
                                     addresses=dict(members)),
        }

    def balance_shard_stats(self):
        if self.fail_stats:
            raise OSError("remote dark")
        return [dict(self._row)]


class _FakeGossip:
    def __init__(self, alive):
        self.alive = set(alive)

    def alive_peers(self, window=None):
        return set(self.alive)


class _FakeGateway:
    def __init__(self, hosts):
        self._hosts = dict(hosts)
        self.routes = RoutingCache(lambda: self._hosts)

    def _live_hosts(self):
        return dict(self._hosts)


class TestRouteFeeder:
    MEMBERS = {1: "nh-a", 2: "nh-b"}

    def _fleet(self, leader_id=1):
        hosts = {
            "nh-a": _FakeHost("nh-a", 1, leader_id, self.MEMBERS),
            "nh-b": _FakeHost("nh-b", 2, leader_id, self.MEMBERS),
        }
        gw = _FakeGateway(hosts)
        return hosts, gw

    def test_tick_learns_leader_from_stats(self):
        hosts, gw = self._fleet(leader_id=1)
        feeder = RouteFeeder(gw, _FakeGossip(["nh-a", "nh-b"]))
        feeder.tick()
        assert gw.routes.lookup(1) == "nh-a"

    def test_gossip_death_overrides_answering_host(self):
        # the host still answers stats, but gossip says it is gone:
        # liveness wins and the stale route is invalidated
        hosts, gw = self._fleet(leader_id=1)
        gossip = _FakeGossip(["nh-a", "nh-b"])
        feeder = RouteFeeder(gw, gossip)
        feeder.tick()
        assert gw.routes.lookup(1) == "nh-a"
        gossip.alive.discard("nh-a")
        hosts["nh-b"]._row["leader_id"] = 0  # no new leader yet
        feeder.tick()
        assert gw.routes.lookup(1) is None
        # the replacement leader is learned as soon as stats show it
        hosts["nh-b"]._row["leader_id"] = 2
        hosts["nh-b"]._row["term"] = 4
        feeder.tick()
        assert gw.routes.lookup(1) == "nh-b"

    def test_collect_failure_invalidates_route(self):
        hosts, gw = self._fleet(leader_id=1)
        feeder = RouteFeeder(gw, None)
        feeder.tick()
        assert gw.routes.lookup(1) == "nh-a"
        hosts["nh-a"].fail_stats = True
        hosts["nh-a"]._closed = True
        hosts["nh-b"]._row["leader_id"] = 0
        feeder.tick()
        assert gw.routes.lookup(1) is None


# ---------------------------------------------------------------------------
# gateway over RPC: 3 in-proc hosts behind RpcServers, leader kill
# ---------------------------------------------------------------------------
def test_gateway_over_rpc_survives_leader_kill():
    reset_inproc_network()
    tag = "rpc-gw"
    addrs = {r: f"{tag}-{r}" for r in (1, 2, 3)}
    nhs, srvs, handles = {}, {}, {}
    for r, a in addrs.items():
        d = f"/tmp/nh-{tag}-{r}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[a] = NodeHost(NodeHostConfig(
            nodehost_dir=d, rtt_millisecond=5, raft_address=a,
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=1, apply_shards=1)),
        ))
    for r, a in addrs.items():
        nhs[a].start_replica(
            addrs, False, AuditKV,
            Config(replica_id=r, shard_id=1, election_rtt=10,
                   heartbeat_rtt=1, pre_vote=True, check_quorum=True),
        )
    gw = feeder = None
    try:
        for a, nh in nhs.items():
            srvs[a] = RpcServer(nh, "127.0.0.1:0")
            srvs[a].start()
            handles[a] = RemoteHostHandle(srvs[a].listen_address,
                                          rtt_millisecond=5)
        gw = Gateway(dict(handles),
                     GatewayConfig(workers=2, default_timeout=5.0,
                                   cap_feedback=False))
        feeder = RouteFeeder(gw, None, interval=0.1)
        feeder.start()
        h = gw.connect(1, timeout=20.0)
        for i in range(5):
            h.sync_propose(audit_set_cmd(f"k{i}", str(i)), timeout=10.0)
        assert gw.read(1, "k0", timeout=10.0) == "0"

        # force leadership onto the alphabetically-FIRST host before
        # killing it: that host is the one _host_for's any_ok sweep
        # tries first, AND the one a follower forwards the first
        # post-kill proposal to — the worst case for the per-attempt
        # propose cap (a random election makes this a 1-in-3 flake)
        first = f"{tag}-1"
        deadline = time.time() + 15
        while not nhs[first].is_leader_of(1) and time.time() < deadline:
            lead = next(
                (a for a, nh in nhs.items() if nh.is_leader_of(1)), None)
            if lead:
                try:
                    nhs[lead].request_leader_transfer(1, 1)
                except RequestError:
                    pass
            time.sleep(0.2)
        assert nhs[first].is_leader_of(1), "leadership transfer stuck"

        # kill the leader HOST (its RPC server keeps answering with
        # NodeHostClosed -> the gateway sees DROPPED and reroutes)
        leader = next(a for a, nh in nhs.items() if nh.is_leader_of(1))
        nhs[leader].close()
        for i in range(5, 10):
            h.sync_propose(audit_set_cmd(f"k{i}", str(i)), timeout=15.0)
        assert gw.read(1, "k9", timeout=10.0) == "9"
        # the feeder converges the cache onto a surviving host
        deadline = time.time() + 10
        while time.time() < deadline:
            r = gw.routes.lookup(1)
            if r is not None and r != leader:
                break
            time.sleep(0.05)
        assert gw.routes.lookup(1) not in (None, leader)
        gw.close_handle(h)
    finally:
        if feeder is not None:
            feeder.close()
        if gw is not None:
            gw.close()
        for h in handles.values():
            h.close()
        for s in srvs.values():
            s.close()
        for nh in nhs.values():
            try:
                nh.close()
            except Exception:  # noqa: BLE001 — leader already closed
                pass


# ---------------------------------------------------------------------------
# the real thing: separate OS processes over TCP
# ---------------------------------------------------------------------------
def test_rpc_smoke_two_process_fleet():
    from dragonboat_tpu.scenario.multiproc import run_rpc_smoke
    out = run_rpc_smoke(n=2, workdir="/tmp/rpc-smoke-test",
                        base_port=30550)
    assert out["committed"] == 8
    assert out["rerouted"]


@pytest.mark.skipif(os.environ.get("DRAGONBOAT_MULTIPROC") != "1",
                    reason="multi-process day: set DRAGONBOAT_MULTIPROC=1")
def test_mini_multiproc_day():
    from dragonboat_tpu.scenario.multiproc import run_mini_multiproc_day
    rep = run_mini_multiproc_day(n=3, workdir="/tmp/mpday-test",
                                 base_port=30650)
    assert rep["audit"] == "ok"
    assert rep["ops"] > 100
    assert set(rep["sla"]) == {"proc_kill9", "asym_drop"}
    # schedule-driven: the byte-stable multiproc plan ran end to end
    assert rep["phases"] == ["warmup", "proc_kill", "asym_partition",
                             "cooldown"]
    from dragonboat_tpu.scenario import DayPlan

    assert rep["plan"] == DayPlan.multiproc(rep["seed"]).describe()
