"""Differential parity: device step kernel vs the scalar oracle.

The oracle itself passes the etcd-style protocol suite
(test_raft_protocol.py); these tests then pin the vectorized kernel to
the oracle bit-for-bit, which transitively pins it to the reference
semantics (reference: internal/raft/raft_etcd_test.go [U] — same
layering: RawNode tests above, step-function parity below).
"""
from __future__ import annotations

import random

import numpy as np

import pytest

from dragonboat_tpu.pb import Entry, EntryType, Message, MessageType

from kernel_harness import Cluster, E, M


def test_single_voter_becomes_leader_and_commits():
    c = Cluster({7: [1]})
    c.run(25)
    assert c.leader_of(7) == 1
    r = c.rafts[(7, 1)]
    assert r.log.committed == r.log.last_index() == 1
    c.step({(7, 1): [c.propose(7, 1, [b"x", b"y"])]})
    assert r.log.committed == 3
    c.compare_state()


def test_three_replica_election_and_heartbeats():
    c = Cluster({1: [1, 2, 3]})
    lid = c.elect(1)
    assert lid is not None
    # all replicas agree on the leader
    for rid in (1, 2, 3):
        assert c.rafts[(1, rid)].leader_id == lid
    # a few heartbeat rounds stay bit-identical
    c.run(20)


def test_replication_and_commit_three_replicas():
    c = Cluster({1: [1, 2, 3]})
    lid = c.elect(1)
    c.step({(1, lid): [c.propose(1, lid, [b"a"])]})
    # deliver replicate + resp rounds
    for _ in range(4):
        c.step(c.deliver_batches(tick=False))
    for rid in (1, 2, 3):
        r = c.rafts[(1, rid)]
        assert r.log.committed == r.log.last_index()
        assert r.log.committed >= 2


def test_follower_forwards_proposal():
    c = Cluster({1: [1, 2, 3]})
    lid = c.elect(1)
    follower = next(r for r in (1, 2, 3) if r != lid)
    c.step({(1, follower): [c.propose(1, follower, [b"fwd"])]})
    for _ in range(5):
        c.step(c.deliver_batches(tick=False))
    assert c.rafts[(1, lid)].log.committed >= 2


def test_five_replicas_with_churn():
    c = Cluster({3: [1, 2, 3, 4, 5]}, election_timeout=8)
    lid = c.elect(3)
    c.step({(3, lid): [c.propose(3, lid, [b"p1", b"p2"])]})
    c.run(30)
    committed = {c.rafts[(3, r)].log.committed for r in (1, 2, 3, 4, 5)}
    assert len(committed) == 1 and committed.pop() >= 3


def test_prevote_and_check_quorum_cluster():
    c = Cluster({9: [1, 2, 3]}, pre_vote=True, check_quorum=True)
    lid = c.elect(9)
    c.step({(9, lid): [c.propose(9, lid, [b"a"])]})
    c.run(40)


def test_many_groups_mixed_sizes():
    c = Cluster({1: [1, 2, 3], 2: [1, 2, 3, 4, 5], 3: [4]})
    for shard in (1, 2, 3):
        c.elect(shard)
    for shard in (1, 2, 3):
        lid = c.leader_of(shard)
        c.step({(shard, lid): [c.propose(shard, lid, [b"v"])]})
        c.run(6, tick=False)
    c.run(15)


def test_witness_and_nonvoting_members():
    c = Cluster(
        {5: [1, 2, 3, 4]},
        witnesses={5: [3]},
        non_votings={5: [4]},
    )
    lid = c.elect(5)
    assert lid in (1, 2)
    c.step({(5, lid): [c.propose(5, lid, [b"w"])]})
    c.run(25)
    # non-voting replica still replicates
    assert c.rafts[(5, 4)].log.committed >= 2


def test_leader_transfer_timeout_now():
    c = Cluster({2: [1, 2, 3]})
    lid = c.elect(2)
    target = next(r for r in (1, 2, 3) if r != lid)
    # host path injects LEADER_TRANSFER; emulate its effect by driving the
    # oracle-visible hot part: catch target up first, then TIMEOUT_NOW
    c.step({(2, lid): [c.propose(2, lid, [b"x"])]})
    c.run(6, tick=False)
    c.step({(2, target): [Message(type=MessageType.TIMEOUT_NOW, term=c.rafts[(2, target)].term)]})
    for _ in range(6):
        c.step(c.deliver_batches(tick=False))
    assert c.leader_of(2) == target


def _transfer(target):
    return Message(type=MessageType.LEADER_TRANSFER, hint=target)


@pytest.mark.parametrize("behind", [False, True])
def test_a_leaders_own_transfer_request_is_hot(behind):
    """PR 32: the request is an inbox slot of the leader's row (hint =
    target).  A target that holds the whole log gets TIMEOUT_NOW in the
    same step; one that is behind is sent what it lacks first, and its
    answer brings the TIMEOUT_NOW.  Every step compared with the oracle
    (``Cluster.step``), ``transfer_target`` and ``election_tick`` among
    the row's fields."""
    c = Cluster({2: [1, 2, 3, 4, 5]}, check_quorum=True, pre_vote=True)
    lid = c.elect(2)
    c.run(4, tick=False)
    target = lid % 5 + 1
    batch = [_transfer(target)]
    if behind:
        batch.insert(0, c.propose(2, lid, [b"x", b"y"]))
    out = c.step({(2, lid): batch})[(2, lid)]
    sent = [m.type for m in out if m.to == target]
    if behind:
        assert MessageType.TIMEOUT_NOW not in sent
        assert MessageType.REPLICATE in sent
    else:
        assert sent == [MessageType.TIMEOUT_NOW]
    g = c.row_of[(2, lid)]
    assert int(np.asarray(c.state.transfer_target)[g]) == target
    assert int(np.asarray(c.state.election_tick)[g]) == 0
    # a proposal during the transfer is dropped, on both sides alike
    c.step(c.deliver_batches(
        tick=False, extra={(2, lid): [c.propose(2, lid, [b"late"])]}))
    for _ in range(8):
        c.step(c.deliver_batches(tick=False))
    assert c.leader_of(2) == target
    assert int(np.asarray(c.state.transfer_target)[g]) == 0  # reset with the role


def test_a_transfer_request_that_cannot_stand_is_ignored():
    c = Cluster({2: [1, 2, 3], 5: [1, 2, 3, 4]}, non_votings={5: [4]})
    lid = c.elect(2)
    lid5 = c.elect(5)
    c.run(4, tick=False)
    g = c.row_of[(2, lid)]
    other = [r for r in (1, 2, 3) if r != lid]
    # self, a replica nobody knows, and (group 5) a non-voter
    c.step({(2, lid): [_transfer(lid), _transfer(9)],
            (5, lid5): [_transfer(4)]})
    assert int(np.asarray(c.state.transfer_target)[g]) == 0
    assert int(np.asarray(c.state.transfer_target)[c.row_of[(5, lid5)]]) == 0
    # a second request while the first is in flight: the first stands
    out = c.step({(2, lid): [_transfer(other[0]), _transfer(other[1])]})
    assert int(np.asarray(c.state.transfer_target)[g]) == other[0]
    assert [m.to for m in out[(2, lid)]
            if m.type == MessageType.TIMEOUT_NOW] == [other[0]]


def test_a_transfer_nobody_answers_is_given_up_after_an_election_window():
    c = Cluster({2: [1, 2, 3]}, election_timeout=6)
    lid = c.elect(2)
    c.run(4, tick=False)
    target = lid % 3 + 1
    g = c.row_of[(2, lid)]
    c.step({(2, lid): [_transfer(target)]})
    for k in c.rows:          # the TIMEOUT_NOW never arrives
        c.net[k].clear()
    tick = Message(type=MessageType.LOCAL_TICK)
    for _ in range(5):
        c.step({(2, lid): [tick]})
        for k in c.rows:
            c.net[k].clear()
    assert int(np.asarray(c.state.transfer_target)[g]) == target
    c.step({(2, lid): [tick]})
    assert int(np.asarray(c.state.transfer_target)[g]) == 0
    assert c.rafts[(2, lid)].is_leader()


def test_a_transfer_request_on_a_row_that_does_not_lead_escalates():
    """The host plans the request for a row its mirror knows as leader;
    should the row have stepped down meanwhile, the kernel hands the
    whole row back (the scalar path forwards the request over the
    wire)."""
    c = Cluster({2: [1, 2, 3]})
    lid = c.elect(2)
    c.run(4, tick=False)
    follower = lid % 3 + 1
    c.allow_escalation = True
    out = c.step({(2, follower): [_transfer(follower % 3 + 1)]})
    assert c.escalations == 1
    assert [(m.type, m.to) for m in out[(2, follower)]] == [
        (MessageType.LEADER_TRANSFER, lid)]


def test_partition_and_rejoin_log_repair():
    """Deposed-leader divergence: the old leader appends uncommitted
    entries in isolation; on rejoin the new leader's log-matching reject
    path repairs it (decrease/retry)."""
    c = Cluster({1: [1, 2, 3]}, election_timeout=6)
    lid = c.elect(1)
    # partition: drop all messages from/to the leader; propose on it
    c.step({(1, lid): [c.propose(1, lid, [b"lost1"])]})
    c.step({(1, lid): [c.propose(1, lid, [b"lost2"])]})
    # throw away everything in flight (the partition)
    for k in c.rows:
        c.net[k].clear()
    # other two elect a new leader (old one gets no ticks: frozen)
    others = [r for r in (1, 2, 3) if r != lid]
    for _ in range(60):
        if any(c.rafts[(1, r)].is_leader() for r in others):
            break
        batches = c.deliver_batches(tick=False)
        for r in others:
            batches.setdefault((1, r), []).insert(
                0, Message(type=MessageType.LOCAL_TICK)
            )
        # old leader stays frozen AND its outbound messages are dropped
        c.step(batches)
        for k in c.rows:
            if k == (1, lid):
                c.net[k].clear()
        c.net[(1, lid)].clear()
    new_lid = next(r for r in others if c.rafts[(1, r)].is_leader())
    c.step({(1, new_lid): [c.propose(1, new_lid, [b"win"])]})
    c.run(4, tick=False)
    # heal: old leader gets traffic again (next heartbeat round reaches it)
    c.run(12)
    r_old = c.rafts[(1, lid)]
    r_new = c.rafts[(1, new_lid)]
    assert not r_old.is_leader()
    assert r_old.log.committed == r_new.log.committed
    assert r_old.log.last_term() == r_new.log.last_term()


@pytest.mark.parametrize("seed", range(6))
def test_randomized_fuzz(seed):
    """Seeded chaos: random ticks, proposals, message drops/dups/delays
    across heterogeneous groups; every step must stay bit-identical."""
    rng = random.Random(0xC0FFEE + seed)
    c = Cluster(
        {1: [1, 2, 3], 2: [1, 2, 3, 4, 5]},
        election_timeout=6,
        heartbeat_timeout=2,
        pre_vote=bool(seed % 2),
        check_quorum=bool(seed % 3 == 0),
    )
    c.allow_escalation = True  # deep lag can exit the W-entry ring window
    for _ in range(120):
        batches = {}
        for key in c.rows:
            msgs = []
            if rng.random() < 0.7:
                msgs.append(Message(type=MessageType.LOCAL_TICK))
            q = c.net[key]
            while q and len(msgs) < M:
                m = q.popleft()
                roll = rng.random()
                if roll < 0.12:
                    continue  # drop
                if roll < 0.2 and len(msgs) < M - 1:
                    msgs.append(m)  # duplicate
                msgs.append(m)
            # random proposal on a random row
            if rng.random() < 0.15 and len(msgs) < M:
                n = rng.randint(1, min(3, E))
                msgs.append(
                    Message(
                        type=MessageType.PROPOSE,
                        entries=tuple(
                            Entry(
                                type=EntryType.APPLICATION,
                                cmd=bytes([rng.randrange(256)]),
                            )
                            for _ in range(n)
                        ),
                    )
                )
            if msgs:
                batches[key] = msgs
        c.step(batches)
    # liveness sanity: at least one group elected some leader at some point
    assert any(r.term > 0 for r in c.rafts.values())


def test_read_index_hot_path_leader():
    """READ_INDEX on the leader row: the kernel must gate on a
    current-term commit, broadcast ctx-carrying heartbeats identical to
    the oracle's, and stay bit-parity through the confirm cycle (the
    synthetic self-resp side channel is excluded by the harness)."""
    c = Cluster({1: [1, 2, 3]})
    lid = c.elect(1)
    key = (1, lid)
    # commit one entry at the leader's term so the read gate passes
    c.step({key: [c.propose(1, lid, [b"v"])]})
    c.run(4, tick=False)
    # a local read: ctx rides the hint fields
    c.step({key: [Message(type=MessageType.READ_INDEX, hint=77, hint_high=88)]})
    # the ctx heartbeats + their responses settle with full state parity
    c.run(3, tick=False)
    assert c.rafts[key].read_index.has_pending() is False


def test_read_index_before_term_commit_is_dropped():
    """Before the leader's no-op barrier commits, reads must be refused
    (oracle: dropped_read_indexes; kernel: reject self-resp + parity)."""
    c = Cluster({1: [1, 2, 3]})
    # drive ticks ONLY until a leader appears — its no-op barrier is
    # appended but cannot have committed (no REPLICATE_RESP delivered,
    # responses still sit in the in-flight net queues)
    lid = None
    for _ in range(200):
        c.step(c.deliver_batches(tick=True))
        if (lid := c.leader_of(1)) is not None:
            break
    assert lid is not None
    key = (1, lid)
    r = c.rafts[key]
    assert r.log.committed < r.log.last_index(), "barrier already committed"
    assert not r.committed_entry_in_current_term()
    c.step({key: [Message(type=MessageType.READ_INDEX, hint=5, hint_high=6)]})
    # the oracle refused the read; the kernel held bit-parity through
    # the same refusal (its reject self-resp is filtered by the harness)
    assert any(
        ctx.low == 5 and ctx.high == 6 for ctx in r.dropped_read_indexes
    ), r.dropped_read_indexes
    assert not r.read_index.has_pending()


def test_fused_multi_tick_slot():
    """Multi-tick fusion: one LOCAL_TICK slot whose log_index carries a
    count advances timers by n — an election timeout fires in ONE slot,
    and a leader's k elapsed heartbeat periods coalesce into ONE
    broadcast (the launch-cost fix that makes 50k-row clusters viable
    on slow backends, and fewer slots per launch everywhere)."""
    import jax
    import numpy as np

    from dragonboat_tpu.ops import kernel as K
    from dragonboat_tpu.ops.types import (
        MT_HEARTBEAT,
        MT_TICK,
        ROLE_LEADER,
        make_inbox,
        make_state,
    )

    # row 0: single voter, election_timeout 10 + jitter < 10 — a count
    # of 20 must elect it in one slot
    G, P, W, M_, E_, O = 2, 3, 8, 2, 1, 16
    peer_ids = np.zeros((G, P), np.int32)
    peer_ids[0, 0] = 1
    peer_ids[1, :3] = [1, 2, 3]
    st = make_state(
        G, P, W,
        shard_ids=np.arange(1, G + 1),
        replica_ids=np.ones(G),
        peer_ids=peer_ids,
        election_timeout=10,
        heartbeat_timeout=2,
    )
    box = make_inbox(G, M_, E_)
    box = box._replace(
        mtype=box.mtype.at[:, 0].set(MT_TICK),
        log_index=box.log_index.at[:, 0].set(20),
    )
    new, out = K.step(st, box, out_capacity=O)
    jax.block_until_ready(new)
    roles = np.asarray(new.role)
    assert roles[0] == ROLE_LEADER, "fused ticks never fired the election"
    # row 1 (3 voters) must have campaigned: vote traffic in the outbox
    assert int(np.asarray(out.count)[1]) > 0

    # leader heartbeat coalescing: 6 fused ticks at heartbeat_timeout=2
    # = 3 periods -> exactly ONE heartbeat per peer
    st2 = new._replace(heartbeat_tick=new.heartbeat_tick * 0)
    box2 = make_inbox(G, M_, E_)
    box2 = box2._replace(
        mtype=box2.mtype.at[:, 0].set(MT_TICK),
        log_index=box2.log_index.at[:, 0].set(6),
    )
    new2, out2 = K.step(st2, box2, out_capacity=O)
    jax.block_until_ready(new2)
    from dragonboat_tpu.ops.types import F_MTYPE

    buf = np.asarray(out2.buf[0])
    n_hb = sum(
        1 for k in range(int(np.asarray(out2.count)[0]))
        if buf[k][F_MTYPE] == MT_HEARTBEAT
    )
    # a single-voter leader has no peers: zero heartbeats
    assert n_hb == 0

    # 3-voter leader: force row 1 to leader, then 6 fused ticks at
    # heartbeat_timeout=2 must emit exactly ONE heartbeat per peer
    st3 = new._replace(
        role=new.role.at[1].set(ROLE_LEADER),
        leader_id=new.leader_id.at[1].set(1),
        heartbeat_tick=new.heartbeat_tick * 0,
        election_tick=new.election_tick * 0,
    )
    new3, out3 = K.step(st3, box2, out_capacity=O)
    jax.block_until_ready(new3)
    buf3 = np.asarray(out3.buf[1])
    hb_targets = [
        int(buf3[k][1])
        for k in range(int(np.asarray(out3.count)[1]))
        if buf3[k][F_MTYPE] == MT_HEARTBEAT
    ]
    assert sorted(hb_targets) == [2, 3], hb_targets


def test_forced_gates_equal_masked_false():
    """Pin the handler no-op invariant behind the lax.cond gating: a
    gate forced OFF (the cond skips the whole handler block) must be
    bit-identical to running every handler with its all-false mask
    (kernel._FORCE_GATES forces every gate open).  A handler with ANY
    unmasked state normalization would diverge here instead of as rare
    batch-composition-dependent corruption in production."""
    import jax
    import numpy as np

    from dragonboat_tpu.ops import kernel as K
    from dragonboat_tpu.ops import sync as S

    from kernel_harness import Cluster, O

    # two independently-traced copies of the un-jitted step: the flag is
    # read at TRACE time, so the first call of each bakes its gating
    # mode into the compiled program (eager _process_slot is minutes of
    # per-op dispatch on CPU; two jit traces are seconds)
    raw_step = K.step.__wrapped__
    base_fn = jax.jit(raw_step, static_argnames=("out_capacity",))
    forced_fn = jax.jit(raw_step, static_argnames=("out_capacity",))

    def run_forced(state, inbox):
        assert not K._FORCE_GATES
        K._FORCE_GATES = True
        try:
            return forced_fn(state, inbox, out_capacity=O)
        finally:
            K._FORCE_GATES = False

    def assert_parity(c, batches):
        ordered = [list(batches.get(k, ())) for k in c.rows]
        inbox, overflow = S.encode_inbox(ordered, M, E)
        assert not overflow
        base_st, base_out = base_fn(c.state, inbox, out_capacity=O)
        forced_st, forced_out = run_forced(c.state, inbox)
        for name in base_st._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(base_st, name)),
                np.asarray(getattr(forced_st, name)),
                err_msg=f"state field {name!r} diverged under forced gates",
            )
        for name in base_out._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(base_out, name)),
                np.asarray(getattr(forced_out, name)),
                err_msg=f"out field {name!r} diverged under forced gates",
            )

    c = Cluster({1: [1, 2, 3]}, pre_vote=True, check_quorum=True)
    # election phase: tick-only and vote-carrying batches leave most
    # gates (propose/read/replicate/rare) closed every step
    for _ in range(12):
        b = c.deliver_batches(tick=True)
        assert_parity(c, b)
        c.step(b)
    lid = c.elect(1)
    key = (1, lid)
    # replication phase: PROPOSE + REPLICATE/RESP traffic, vote gates
    # closed
    b = c.deliver_batches(tick=False, extra={key: [c.propose(1, lid, [b"a"])]})
    assert_parity(c, b)
    c.step(b)
    for _ in range(4):
        b = c.deliver_batches(tick=False)
        assert_parity(c, b)
        c.step(b)
    # one step per rare/cold-path hot type, everything else closed
    follower = next(r for r in (1, 2, 3) if r != lid)
    for m in (
        Message(type=MessageType.READ_INDEX, hint=7, hint_high=9),
        Message(type=MessageType.UNREACHABLE, from_=follower),
        Message(type=MessageType.SNAPSHOT_STATUS, from_=follower, reject=True),
    ):
        b = {key: [m]}
        assert_parity(c, b)
        c.step(b)
        b = c.deliver_batches(tick=False)
        if b:
            assert_parity(c, b)
            c.step(b)
    # leadership transfer exercises the TIMEOUT_NOW gate on a follower
    b = {
        (1, follower): [
            Message(
                type=MessageType.TIMEOUT_NOW,
                from_=lid,
                to=follower,
                term=c.rafts[key].term,
            )
        ]
    }
    assert_parity(c, b)
    # the purest form: an all-empty inbox — every gate off vs every
    # handler under an all-false mask
    assert_parity(c, {})
