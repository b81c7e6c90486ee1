"""The device lease renewed every launch (PR 31).

The kernel carries "a peer answered since this row's last tick feed" as
bit 1 of the ``active`` lane and reports a quorum of it as
``F_QUORUM_FRESH``; the colocated engine keeps each resident leader's
lease age in ``hostplane.LeaseAges`` and ``Node.lease_probe`` reads it.
Here: the bit (set only by a response handled after the tick slot,
cleared by the next feed, bit 0 and the CheckQuorum sweep as they were),
the flag (self a voter, a quorum, witnesses counted as the window bit
counts them), the probe's race with a row that changes hands, and the
safety run: a resident leader cut off from its followers under a lease
reader and a writer, audited.  Counts and relations only: a CPU run
tells no time that matters.

(The file's name sorts last on purpose: tier-1 hands files to its six
workers in collection order, several older files share ``/tmp/nh-*``
directories and pass or collide by which of them overlap, and a new
file in the middle of the order would shift every later one.)
"""
import shutil
import threading
import time
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    FaultController,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.audit import (
    AuditClient,
    AuditKV,
    HistoryRecorder,
    run_audit,
    settle_journals,
)
from dragonboat_tpu.node import (
    LEASE_HELD,
    LEASE_MISS_EXPIRING,
    LEASE_MISS_NOT_LEADER,
    Node,
)
from dragonboat_tpu.ops import hostplane
from dragonboat_tpu.ops import kernel as K
from dragonboat_tpu.ops import sync as S
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.ops.engine import _summarize_flags
from dragonboat_tpu.ops.types import (
    ACTIVE_FRESH,
    ACTIVE_LIVE,
    F_ANY_LIVE,
    F_QUORUM_ACTIVE,
    F_QUORUM_FRESH,
    KIND_NON_VOTING,
    KIND_VOTER,
    KIND_WITNESS,
    ROLE_FOLLOWER,
    ROLE_LEADER,
    make_out,
    make_state,
)
from dragonboat_tpu.pb import Message, MessageType
from dragonboat_tpu.transport.inproc import reset_inproc_network

import kernel_harness as KH
from raft_harness import Network
from test_tick_lane import parity_oracle

BOTH = ACTIVE_LIVE | ACTIVE_FRESH
TICK = Message(type=MessageType.LOCAL_TICK)


# -- (a) the kernel's bit ------------------------------------------------
def _leader_with_two_answers(kind):
    """A three-voter group on the oracle and the device, its leader's
    network queue holding one answer from each follower."""
    c = KH.Cluster({1: [1, 2, 3]}, check_quorum=True, election_timeout=10,
                   heartbeat_timeout=2)
    lid = c.elect(1)
    key = (1, lid)
    while any(c.net[k] for k in c.rows):
        c.step(c.deliver_batches())
    if kind == "heartbeat":
        c.step({key: [TICK]})
        c.step({key: [TICK]})  # heartbeat_timeout 2: the broadcast
        want = MessageType.HEARTBEAT_RESP
    else:
        c.step({key: [c.propose(1, lid, [b"x"])]})
        want = MessageType.REPLICATE_RESP
    followers = [k for k in c.rows if k != key]
    assert all(c.net[k] for k in followers)
    c.step({k: [c.net[k].popleft()] for k in followers})
    answers = list(c.net[key])
    c.net[key].clear()
    assert [m.type for m in answers] == [want, want]
    return c, key, answers


def _lane(c, key, peer):
    g = c.row_of[key]
    slot = int(np.nonzero(np.asarray(c.state.peer_id)[g] == peer)[0][0])
    return int(np.asarray(c.state.active)[g, slot])


def _flags(c, key):
    G = len(c.rows)
    word = np.asarray(_summarize_flags(
        c.state, c.state, make_out(G, KH.P, KH.M, KH.E, KH.O)))
    return int(word[c.row_of[key]])


@pytest.mark.parametrize("kind", ["heartbeat", "replicate"])
def test_the_fresh_bit_is_set_after_the_tick_feed_and_cleared_by_the_next(
        kind):
    """One answer before the launch's tick slot and one after it: both
    set bit 0, only the later one keeps bit 1, and the next feed clears
    it; the oracle agrees on bit 0 at every step (``Cluster.step``
    compares the whole row)."""
    c, key, (early, late) = _leader_with_two_answers(kind)
    c.step({key: [early, TICK, late]})
    assert _lane(c, key, early.from_) == ACTIVE_LIVE  # lost to the clear
    assert _lane(c, key, late.from_) == BOTH
    # self and one of two others: a quorum of three, on both bits
    assert _flags(c, key) & F_QUORUM_FRESH
    assert _flags(c, key) & F_QUORUM_ACTIVE
    # a launch that feeds no tick leaves the bit standing ...
    c.step({})
    assert _lane(c, key, late.from_) == BOTH
    # ... and the next feed clears it, bit 0 untouched
    c.step({key: [TICK]})
    assert _lane(c, key, early.from_) == ACTIVE_LIVE
    assert _lane(c, key, late.from_) == ACTIVE_LIVE
    assert not _flags(c, key) & F_QUORUM_FRESH
    assert _flags(c, key) & F_QUORUM_ACTIVE
    while any(c.net[k] for k in c.rows):  # the feed's heartbeats
        c.step(c.deliver_batches())


def test_a_fresh_leader_has_bit_0_fabricated_and_never_bit_1():
    c = KH.Cluster({1: [1, 2, 3]}, check_quorum=True, election_timeout=10,
                   heartbeat_timeout=2)
    key = (1, 2)
    # drive to the step the winner is elected in, and no further
    for _ in range(200):
        if c.rafts[key].is_leader() or c.leader_of(1) is not None:
            break
        c.step(c.deliver_batches(tick=True))
    lid = c.leader_of(1)
    g = c.row_of[(1, lid)]
    lanes = np.asarray(c.state.active)[g]
    valid = np.asarray(c.state.peer_id)[g] != 0
    assert (lanes[valid] == ACTIVE_LIVE).all()
    assert not _flags(c, (1, lid)) & F_QUORUM_FRESH
    assert _flags(c, (1, lid)) & F_QUORUM_ACTIVE


def _three_voter_rows(G, **over):
    peer_ids = np.broadcast_to(np.array([1, 2, 3], np.int32), (G, 3)).copy()
    st = make_state(
        G, 3, 8, shard_ids=np.arange(1, G + 1, dtype=np.int32),
        replica_ids=np.ones((G,), np.int32), peer_ids=peer_ids,
        election_timeout=10, heartbeat_timeout=2, check_quorum=True,
        **{k: v for k, v in over.items() if k == "peer_kinds"})
    cols = {k: np.asarray(getattr(st, k)).copy() for k in
            ("role", "active", "check_quorum", "election_tick", "term")}
    cols["role"][:] = ROLE_LEADER
    cols["term"][:] = 1
    return st, cols


def test_the_sweep_counts_bit_0_alone_and_leaves_bit_1_standing():
    """CheckQuorum reads and clears bit 0: a leader whose lanes carry
    only bit 1 steps down at its window's end, one with bit 0 stays;
    and a sweep that arrives as a message (no tick feed) leaves bit 1
    as it found it."""
    G = 3
    st, cols = _three_voter_rows(G)
    cols["active"][0] = [0, ACTIVE_FRESH, ACTIVE_FRESH]  # no liveness
    cols["active"][1] = [0, ACTIVE_LIVE, 0]              # a quorum alive
    cols["active"][2] = [0, BOTH, BOTH]
    cols["election_tick"][:2] = 9  # the next tick ends the window
    st = st._replace(**{k: jnp.asarray(v) for k, v in cols.items()})
    inbox, overflow = S.encode_inbox(
        [[TICK], [TICK], [Message(type=MessageType.CHECK_QUORUM)]], 4, 2)
    assert not overflow
    new, out = K.step(st, inbox, out_capacity=16)
    assert not np.asarray(out.escalate).any()
    role = np.asarray(new.role)
    active = np.asarray(new.active)
    assert role[0] == ROLE_FOLLOWER
    assert role[1] == ROLE_LEADER and (active[1] == 0).all()
    # a sweep by message: bit 0 cleared, bit 1 standing
    assert role[2] == ROLE_LEADER
    assert active[2].tolist() == [0, ACTIVE_FRESH, ACTIVE_FRESH]


CASES = {
    # name: (lanes of the two other peers, peer kinds, role, check_quorum)
    "one_other_of_three": ([ACTIVE_FRESH, 0], None, ROLE_LEADER, 1),
    "both_bits": ([BOTH, 0], None, ROLE_LEADER, 1),
    "self_only": ([0, 0], None, ROLE_LEADER, 0 + 1),
    "window_bit_only": ([ACTIVE_LIVE, ACTIVE_LIVE], None, ROLE_LEADER, 1),
    "check_quorum_off": ([BOTH, BOTH], None, ROLE_LEADER, 0),
    "follower": ([BOTH, BOTH], None, ROLE_FOLLOWER, 1),
    "a_witness_answers": (
        [0, ACTIVE_FRESH], [KIND_VOTER, KIND_VOTER, KIND_WITNESS],
        ROLE_LEADER, 1),
    "a_non_voter_answers": (
        [0, ACTIVE_FRESH], [KIND_VOTER, KIND_VOTER, KIND_NON_VOTING],
        ROLE_LEADER, 1),
    "self_not_a_voter": (
        [BOTH, BOTH], [KIND_WITNESS, KIND_VOTER, KIND_VOTER],
        ROLE_LEADER, 1),
}
FRESH_UP = {"one_other_of_three", "both_bits", "a_witness_answers"}
ACTIVE_UP = {"both_bits", "window_bit_only"}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_fresh_flag_needs_a_voter_leader_and_a_quorum(case):
    """``F_QUORUM_FRESH`` is ``F_QUORUM_ACTIVE``'s arithmetic over bit
    1: self a voter, a quorum of voting lanes (witnesses count, non-
    voters do not), CheckQuorum on, a leader; the two bits are read
    apart; neither promotes a row into the values set."""
    lanes, kinds, role, cq = CASES[case]
    over = {} if kinds is None else {
        "peer_kinds": np.asarray([kinds], np.int32)}
    st, cols = _three_voter_rows(1, **over)
    cols["active"][0] = [0] + lanes
    cols["role"][0] = role
    cols["check_quorum"][0] = cq
    st = st._replace(**{k: jnp.asarray(v) for k, v in cols.items()})
    word = int(np.asarray(_summarize_flags(st, st, make_out(1, 3, 4, 2, 8)))[0])
    assert bool(word & F_QUORUM_FRESH) == (case in FRESH_UP)
    assert bool(word & F_QUORUM_ACTIVE) == (case in ACTIVE_UP)
    assert not word & F_ANY_LIVE
    assert not F_ANY_LIVE & (F_QUORUM_FRESH | F_QUORUM_ACTIVE)


# -- (d) the probe's race ------------------------------------------------
def _probe_node(r):
    """What ``Node.lease_probe`` reads of a node, around a scalar
    leader whose own remotes hold no evidence."""
    for rm in r.all_remotes().values():
        rm.last_resp_tick = -1
    assert r.lease_remaining_ticks() == 0
    return SimpleNamespace(
        stopped=False, stopping=False, lease_cell=None,
        peer=SimpleNamespace(raft=r, is_leader=r.is_leader),
        sm=SimpleNamespace(last_applied=r.log.committed))


def _leader_raft():
    net = Network.of(3, check_quorum=True)
    net.elect(1)
    net.propose(1)
    r = net.peers[1]
    assert r.committed_entry_in_current_term()
    return r


def test_a_row_that_changes_hands_between_the_probes_loads_yields_no_lease():
    """The row is released and armed again for ANOTHER node between a
    probe's load of the age and its look at the token: the old node is
    told no lease, never the other node's; the new node reads its own."""
    old, new = _probe_node(_leader_raft()), _probe_node(_leader_raft())
    owner = {2: old}
    lanes = hostplane.LeaseAges(4, 3, node_of=owner.__getitem__)
    et = old.peer.raft.election_timeout
    fresh = np.full((4,), F_QUORUM_FRESH, np.int32)
    one = np.asarray([2])

    lanes.arm(2, et)
    assert old.lease_cell == (lanes, 2, int(lanes.token[2]))
    assert Node.lease_probe(old, 2) == (LEASE_MISS_EXPIRING, 0)  # no anchor
    lanes.lanes_step(one, np.asarray([3]), np.asarray([3]), fresh)
    assert Node.lease_probe(old, 2) == (LEASE_HELD, et - 3)
    stale_cell = old.lease_cell

    # the race itself, at the one point it can bite: the age loaded is
    # already the other node's, the token is looked at after
    class Racing:
        """``lanes.age`` for one probe: hands the row over to ``new``
        (release, attach, arm, a renewing launch) as the probe loads
        the element, and returns what is there AFTERWARDS."""

        def __getitem__(self, g):
            lanes.disarm(g)
            owner[g] = new
            lanes.arm(g, et)
            lanes.lanes_step(one, np.asarray([1]), np.asarray([1]), fresh)
            return real_age[g]

    real_age = lanes.age
    old.lease_cell = (SimpleNamespace(age=Racing(), token=lanes.token), 2,
                      stale_cell[2])
    assert Node.lease_probe(old, 2) == (LEASE_MISS_EXPIRING, 0)
    assert real_age[2] == 1  # the other node's lease, fresh and unread
    assert Node.lease_probe(new, 2) == (LEASE_HELD, et - 1)
    # the cell the old node kept from before the handover is dead too
    old.lease_cell = stale_cell
    assert Node.lease_probe(old, 2) == (LEASE_MISS_EXPIRING, 0)
    # and a disarm takes the holder's cell back
    lanes.disarm(2)
    assert new.lease_cell is None and lanes.age[2] == hostplane.LEASE_NONE
    assert Node.lease_probe(new, 2) == (LEASE_MISS_EXPIRING, 0)


def test_the_lease_cell_keeps_the_scalar_gates():
    """The four gates stand in their order in front of the cell, a
    transfer in flight zeroes it, a leader removed from the voters
    reports none, and while the engine holds the row the lane alone
    stands: the remotes' anchors, on this replica's clock only, serve
    a replica the engine does not step."""
    r = _leader_raft()
    node = _probe_node(r)
    lanes = hostplane.LeaseAges(2, 3, node_of=lambda g: node)
    et = r.election_timeout
    lanes.arm(0, et)
    lanes.lanes_step(np.asarray([0]), np.asarray([4]), np.asarray([4]),
                     np.full((2,), F_QUORUM_FRESH, np.int32))
    assert Node.lease_probe(node, 2) == (LEASE_HELD, et - 4)
    # fresher anchors in the scalar remotes change nothing for a row
    # the engine steps; they stand once it has let the row go
    r.tick_count += 5
    r.anchor_quorum_evidence(r.tick_count - 1)
    assert Node.lease_probe(node, 2) == (LEASE_HELD, et - 4)
    lanes.disarm(0)
    assert node.lease_cell is None
    assert Node.lease_probe(node, 2) == (LEASE_HELD, et - 1)
    lanes.arm(0, et)
    assert Node.lease_probe(node, 2) == (LEASE_MISS_EXPIRING, 0)
    lanes.lanes_step(np.asarray([0]), np.asarray([4]), np.asarray([4]),
                     np.full((2,), F_QUORUM_FRESH, np.int32))
    for rm in r.all_remotes().values():
        rm.last_resp_tick = -1
    r.leader_transfer_target = 2
    assert Node.lease_probe(node, 2) == (LEASE_MISS_EXPIRING, 0)
    r.leader_transfer_target = 0
    me = r.remotes.pop(r.replica_id)
    assert Node.lease_probe(node, 2) == (LEASE_MISS_EXPIRING, 0)
    r.remotes[r.replica_id] = me
    assert Node.lease_probe(node, 2)[0] == LEASE_HELD
    node.stopping = True
    assert Node.lease_probe(node, 2) == (LEASE_MISS_NOT_LEADER, 0)


# -- the safety run ------------------------------------------------------
ADDRS = {1: "lease-safe-1", 2: "lease-safe-2", 3: "lease-safe-3"}
ET = 20  # election_rtt: the lease's length in ticks
MARGIN = 2  # the gateway's lease_margin_ticks


class SafetyCluster:
    """Three NodeHosts on one ColocatedEngineGroup running AuditKV,
    with the fault plane on their transports."""

    def __init__(self, workdir, seed):
        reset_inproc_network()
        self.group = ColocatedEngineGroup(
            capacity=16, P=5, W=32, M=8, E=4, O=32, budget=4)
        self.nemesis = FaultController(seed=seed)
        self.nhs = {}
        for rid, addr in ADDRS.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=f"{workdir}/nh-{rid}", rtt_millisecond=5,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=self.group.factory,
                ),
            ))
            self.nemesis.install_nodehost(rid, self.nhs[rid])
        for rid, nh in self.nhs.items():
            nh.start_replica(ADDRS, False, AuditKV, Config(
                replica_id=rid, shard_id=1, election_rtt=ET,
                heartbeat_rtt=2, pre_vote=True, check_quorum=True))
        self.core = self.group.core

    def leader(self, among=ADDRS, deadline=30.0):
        end = time.time() + deadline
        while time.time() < end:
            for rid in among:
                if self.nhs[rid].is_leader_of(1):
                    return rid
            time.sleep(0.01)
        raise AssertionError("no leader")

    def cut_off(self, rid):
        """Cut ``rid`` off from the others on both layers the group
        talks through, at a point where no generation is in flight, and
        return its node's clock at that point: every launch from here
        on routes nothing between the two sides."""
        self.nemesis.set_partition({ADDRS[rid]})
        core = self.core
        with core._lock:
            core._fence()
            core._part_fn = lambda s, r: 1 if r == rid else 0
            core._tables_dirty = True
            return self.nhs[rid]._nodes[1].tick_count

    def heal(self):
        self.nemesis.heal_wire()
        self.core.set_partition(None)

    def close(self):
        self.nemesis.stop()
        for nh in self.nhs.values():
            nh.close()


@pytest.mark.parametrize("ticker", ["running", "stopped"])
def test_a_cut_off_leader_serves_no_lease_read_past_its_lease(
        tmp_path, ticker):
    """The guarantee, run: a resident leader is cut off from its
    followers while one client keeps reading on its lease and another
    writes through whichever leader stands.  The recorded history is
    linearizable (Wing–Gong), NO read is served on the lease once the
    new leader has committed in its term — however the launches fed
    the rows: the lease is counted on the clock of the voter furthest
    ahead — the old leader's probe never says ``LEASE_HELD`` once
    ``ET`` less the margin ticks of its own clock have passed since
    the cut and says ``LEASE_MISS_EXPIRING`` from there on until
    CheckQuorum deposes it; all of it with the array passes held
    against their per-row twins.  ``stopped``: the old leader's ticker
    stands still from the cut on (``NodeHost.pause_ticks``), the
    worst a starved row can come to: its own clock never moves again,
    nothing ever deposes it, and its lease still ends by its peers'
    clocks before either of them may vote."""
    with parity_oracle() as differences:
        c = SafetyCluster(str(tmp_path), seed=31)
        rec = HistoryRecorder()
        stop = threading.Event()
        threads = []
        try:
            old = c.leader()
            nh_old = c.nhs[old]
            node = nh_old._nodes[1]
            writer = AuditClient(lambda: c.nhs, 1, rec, seed=31,
                                 op_timeout=6.0, per_try_timeout=0.5)
            assert writer.register()
            writer.write("a")

            served = []   # (clock of the old leader at the answer, op)
            probes = []   # (clock, reason) of every probe by the reader
            reader_id = rec.new_client()

            def read_on_the_lease():
                while not stop.is_set():
                    op = rec.invoke(reader_id, "r", "a")
                    tc = node.tick_count
                    why, value = nh_old.lease_read(1, ("get", "a"), MARGIN)
                    if why == LEASE_HELD:
                        rec.ok(op, value)
                        served.append((tc, op))
                    else:
                        rec.fail(op)
                    probes.append((tc, why))
                    time.sleep(0.001)

            def write_along():
                while not stop.is_set():
                    writer.write("a")
                    time.sleep(0.002)

            # the first commit of a leader on the other side, as seen by
            # a poll (so: no earlier than it happened)
            first_commit = []
            clocks_then = {}  # every replica's clock when it was seen

            def clocks():
                return {rid: nh._nodes[1].tick_count
                        for rid, nh in c.nhs.items()}

            def watch_the_other_side():
                others = [r for r in ADDRS if r != old]
                while not stop.is_set() and not first_commit:
                    for rid in others:
                        r = c.nhs[rid]._nodes[1].peer.raft
                        if (
                            c.nhs[rid].is_leader_of(1)
                            and r.committed_entry_in_current_term()
                        ):
                            first_commit.append(time.monotonic())
                            clocks_then.update(clocks())
                            break
                    time.sleep(0.001)

            for fn in (read_on_the_lease, write_along):
                threads.append(threading.Thread(target=fn, daemon=True))
                threads[-1].start()
            end = time.time() + 30
            while time.time() < end and len(served) < 50:
                time.sleep(0.01)
            assert len(served) >= 50, "the lease never held"
            assert c.core._row_of.get((1, old)) is not None

            if ticker == "stopped":
                nh_old.pause_ticks()
            tc_cut = c.cut_off(old)
            t_cut = time.monotonic()
            clocks_cut = clocks()
            threads.append(threading.Thread(
                target=watch_the_other_side, daemon=True))
            threads[-1].start()
            new = c.leader(among=[r for r in ADDRS if r != old])
            end = time.time() + 30
            while time.time() < end and not first_commit:
                time.sleep(0.01)
            assert first_commit, "the other side never committed"
            # let the writer land a few writes under the new leader
            # while the old one is still cut off and still being read
            n0 = sum(1 for o in rec.ops() if o.kind == "w" and
                     o.status == "ok" and o.invoke > first_commit[0])
            end = time.time() + 30
            while time.time() < end:
                n = sum(1 for o in rec.ops() if o.kind == "w" and
                        o.status == "ok" and o.invoke > first_commit[0])
                if n >= n0 + 5:
                    break
                time.sleep(0.02)
            assert n >= n0 + 5, "no write went through the new leader"
            if ticker == "stopped":
                # still the leader by its own lights, with nothing to
                # depose it: only the lease stands between it and a
                # stale read
                assert nh_old.is_leader_of(1)
                assert node.lease_probe(MARGIN)[0] == LEASE_MISS_EXPIRING
                g_old = c.core._row_of[(1, old)]
                lane_age = int(c.core._lease.age[g_old])
                own_age = int(c.core._lease.own[g_old])
                # by its peers' clocks, not its own (which took what
                # backlog it had at the cut and then stood)
                assert own_age < lane_age >= ET - MARGIN, (own_age, lane_age)
                nh_old.resume_ticks()
            c.heal()
            time.sleep(0.5)
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            writer.close()

            after = [(tc, why) for tc, why in probes if tc >= tc_cut]
            held_after = [tc for tc, why in after if why == LEASE_HELD]
            # held only inside the lease: the anchor is no later than
            # the cut, so the lease is at least (clock - cut) old, and
            # served only while more than the margin of its ET is left
            assert all(tc - tc_cut < ET - MARGIN for tc in held_after), (
                tc_cut, max(held_after))
            # it runs out, and says so, before CheckQuorum deposes the
            # leader: the clock moves a completion at a time (up to
            # ET // 2 ticks), so "by ET ticks after the cut" reads "at
            # the first completion at or past the lease's end"
            assert any(why == LEASE_MISS_EXPIRING for _tc, why in after)
            past = [why for tc, why in after if tc - tc_cut >= ET - MARGIN]
            assert past and past[0] in (
                LEASE_MISS_EXPIRING, LEASE_MISS_NOT_LEADER), past[:3]
            # NO read on the lease once the other side has committed,
            # whatever the skew between the replicas' clocks (measured
            # all the same, for the message: every replica's clock at
            # the cut and when the commit was seen).  The lease's age
            # goes by the clock of the voter furthest ahead, and the
            # completion that counts a launch's ticks runs before
            # anything of that launch is handed on
            late = [(tc - tc_cut, round(op.ret - first_commit[0], 4))
                    for tc, op in served if op.ret > first_commit[0]]
            since_cut = {rid: clocks_then[rid] - clocks_cut[rid]
                         for rid in clocks_cut}
            assert not late, dict(
                late=late[:5], n_late=len(late), old=old, new=new,
                commit_after_cut_s=round(first_commit[0] - t_cut, 4),
                ticks_since_cut=since_cut)
            if ticker == "stopped":
                assert since_cut[old] < min(
                    v for rid, v in since_cut.items() if rid != old)
            assert new != old
            journals = settle_journals(c.nhs, 1, timeout=30.0)
            report = run_audit(rec.ops(), journals)
            assert report.ok, report.describe()
            counts = rec.counts()
            assert counts.get("ok", 0) > 60, counts
            st = c.core.stats
            assert st["lease_rows_armed"] > 0
            assert 0 < st["lease_rows_fresh"] <= st["lease_rows_armed"]
            assert st["divergence_halts"] == 0
            assert differences() == 0, hostplane.PARITY_FAILURES[:3]
        finally:
            stop.set()
            c.close()
            shutil.rmtree(str(tmp_path), ignore_errors=True)
