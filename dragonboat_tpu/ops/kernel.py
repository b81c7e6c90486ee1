"""The vectorized raft step kernel.

``step(state, inbox) -> (state', DeviceOut)`` advances **every row at
once** through an ordered inbox of M message slots.  Slot i is processed
for all G rows in parallel (one masked pass over the whole batch), and
slots are processed sequentially — exactly the order the scalar oracle
(`dragonboat_tpu.raft.raft.Raft.handle`) would process the same messages,
which is what makes bit-exact differential testing possible.

The semantics mirror the oracle function-for-function (which itself
mirrors reference internal/raft/raft.go [U]); each helper cites its
oracle counterpart.  Everything here is pure int32 math — no host
callbacks, no dynamic shapes, no data-dependent Python control flow —
so XLA compiles it to a single fused program that scales to 100k+ rows
(BASELINE north star).

Lane packing: the public layout keeps G (rows) on the MAJOR axis —
``[G, P]`` peer slots, ``[G, W]`` ring, ``[G, M]`` inboxes — because
that is the natural host-side indexing.  On TPU the MINOR axis maps to
the 128-wide lane dimension, so a [G, P] int32 operand with P=3..8 pads
the lanes 16-42x and every pass over the state moved that much dead
HBM traffic (the r4 ledger's residual ~1 us/row/slot).  The kernel
therefore runs **G-last internally**: ``step`` transposes the state,
inbox and outbox to ``[P, G]`` / ``[W, G]`` / ``[M, G]`` /
``[O, N_FIELDS, G]`` at the boundary (two cheap contiguous copies,
~100 MB/launch at 300k rows) and every per-slot op streams fully packed
lanes.  All helpers in this file expect the INTERNAL layout; the
``step`` contract (external layout in/out) is unchanged.

Escalation contract: if a row needs anything the device cannot resolve
(log term outside the W-ring, outbox overflow, a cold message type) its
ESC bit is set in ``out.escalate``; the host replays that row's inbox on
the scalar oracle from the pre-step snapshot and discards every
device-side effect for the row (state column, outbox rows, aux outputs).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .types import (
    ACTIVE_FRESH,
    ACTIVE_LIVE,
    APPEND_LO_NONE,
    DeviceOut,
    DeviceState,
    ESC_COLD,
    ESC_INVARIANT,
    ESC_OVERFLOW,
    ESC_WINDOW,
    F_SRC_SLOT,
    HOT_TYPES,
    I32,
    Inbox,
    KIND_NON_VOTING,
    KIND_VOTER,
    KIND_WITNESS,
    MT_CHECK_QUORUM,
    MT_ELECTION,
    MT_HEARTBEAT,
    MT_HEARTBEAT_RESP,
    MT_INSTALL_SNAPSHOT,
    MT_PROPOSE,
    MT_READ_INDEX,
    MT_READ_INDEX_RESP,
    MT_REPLICATE,
    MT_REPLICATE_RESP,
    MT_REQUEST_PREVOTE,
    MT_REQUEST_PREVOTE_RESP,
    MT_REQUEST_VOTE,
    MT_REQUEST_VOTE_RESP,
    MT_SNAPSHOT_RECEIVED,
    MT_SNAPSHOT_STATUS,
    MT_TICK,
    MT_LEADER_TRANSFER,
    MT_TIMEOUT_NOW,
    MT_UNREACHABLE,
    N_FIELDS,
    ROLE_CANDIDATE,
    ROLE_FOLLOWER,
    ROLE_LEADER,
    ROLE_NON_VOTING,
    ROLE_PRE_CANDIDATE,
    ROLE_WITNESS,
    RS_REPLICATE,
    RS_RETRY,
    RS_SNAPSHOT,
    RS_WAIT,
    SLOT_DROPPED,
    SLOT_FORWARDED,
    make_out,
)

# test hook (tests/test_kernel_parity.py): True forces every lax.cond
# handler gate in _process_slot open, so each handler also runs under
# an all-false mask — pinning the handler no-op invariant documented at
# the campaign section header.  Read at trace time; never set this in
# production code.
_FORCE_GATES = False

# ---------------------------------------------------------------------------
# internal (G-last) layout plumbing
# ---------------------------------------------------------------------------
# state fields that carry a per-peer or per-ring axis; everything else is [G]
_PEER_FIELDS = (
    "peer_id",
    "peer_kind",
    "match",
    "next_idx",
    "rstate",
    "snap_index",
    "active",
    "granted",
)
_RING_FIELDS = ("ring_term", "ring_cc")


def _state_to_internal(st: DeviceState) -> DeviceState:
    """[G, P] -> [P, G], [G, W] -> [W, G]; [G] fields untouched."""
    return st._replace(
        **{f: getattr(st, f).T for f in _PEER_FIELDS + _RING_FIELDS}
    )


# the transpose is its own inverse
_state_from_internal = _state_to_internal


def _inbox_to_internal(ib: Inbox) -> Inbox:
    """[G, M] -> [M, G]; [G, M, E] -> [M, E, G]."""
    return Inbox(
        **{
            f: (
                getattr(ib, f).transpose(1, 2, 0)
                if getattr(ib, f).ndim == 3
                else getattr(ib, f).T
            )
            for f in Inbox._fields
        }
    )


def _make_out_internal(G: int, P: int, M: int, E: int, O: int) -> DeviceOut:
    # derived from the canonical external constructor so sentinel values
    # (SLOT_UNUSED, APPEND_LO_NONE, barrier -1) have one source of truth;
    # under jit the transposes of fresh constants fold away
    return _out_to_internal(make_out(G, P, M, E, O))


def _out_to_internal(out: DeviceOut) -> DeviceOut:
    return out._replace(
        buf=out.buf.transpose(1, 2, 0),
        need_snapshot=out.need_snapshot.T,
        slot_base=out.slot_base.T,
        slot_term=out.slot_term.T,
        ent_drop=out.ent_drop.transpose(1, 2, 0),
    )


def _out_from_internal(out: DeviceOut) -> DeviceOut:
    return out._replace(
        buf=out.buf.transpose(2, 0, 1),
        need_snapshot=out.need_snapshot.T,
        slot_base=out.slot_base.T,
        slot_term=out.slot_term.T,
        ent_drop=out.ent_drop.transpose(2, 0, 1),
    )


def _P(st: DeviceState) -> int:
    """Peer-slot count in the internal [P, G] layout (st.P reads shape[1],
    which is G here)."""
    return st.peer_id.shape[0]


def _W(st: DeviceState) -> int:
    return st.ring_term.shape[0]


def _w(mask, new, old):
    """Masked field update; mask is [G], fields are [G] or [..., G] — the
    trailing-G layout makes mask broadcasting automatic."""
    return jnp.where(mask, new, old)


def _wp(mask_pg, new, old):
    """Masked per-(peer, row) update; mask is [P, G]."""
    return jnp.where(mask_pg, new, old)


# ---------------------------------------------------------------------------
# deterministic election jitter (mirrors raft.splitmix32 / election_jitter)
# ---------------------------------------------------------------------------
def _splitmix32(x):
    x = (x.astype(jnp.uint32) + jnp.uint32(0x9E3779B9))
    z = x
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x85EBCA6B)
    z = z ^ (z >> 13)
    z = z * jnp.uint32(0xC2B2AE35)
    z = z ^ (z >> 16)
    return z


def _jitter(shard_id, replica_id, seq, span):
    h = _splitmix32(
        (shard_id.astype(jnp.uint32) << 24)
        ^ (replica_id.astype(jnp.uint32) << 8)
        ^ seq.astype(jnp.uint32)
    )
    return (h % span.astype(jnp.uint32)).astype(I32)


def reset_timeout(st: DeviceState, mask) -> DeviceState:
    """oracle: Raft._reset_randomized_timeout.  Touches only [G] fields,
    so it works on both the external and internal layouts."""
    seq = st.timeout_seq + 1
    rt = st.election_timeout + _jitter(
        st.shard_id, st.replica_id, seq, st.election_timeout
    )
    return st._replace(
        timeout_seq=_w(mask, seq, st.timeout_seq),
        rand_timeout=_w(mask, rt, st.rand_timeout),
    )


# ---------------------------------------------------------------------------
# peer-slot helpers (internal layout: peer arrays are [P, G])
# ---------------------------------------------------------------------------
def _valid(st):
    return st.peer_id != 0


def _voters(st):
    """Voting members = voters + witnesses (oracle: voting_members)."""
    return _valid(st) & (
        (st.peer_kind == KIND_VOTER) | (st.peer_kind == KIND_WITNESS)
    )


def _num_voters(st):
    return jnp.sum(_voters(st), axis=0).astype(I32)


def _quorum(st):
    return _num_voters(st) // 2 + 1


def _self_kind(st):
    return _col(st.peer_kind, st.self_slot)


def _self_is_voter(st):
    """True when this replica currently appears as a voter slot."""
    return (_col(st.peer_id, st.self_slot) == st.replica_id) & (
        _self_kind(st) == KIND_VOTER
    )


def _slot_of(st, pid):
    """Peer-axis slot holding replica ``pid`` [G] -> (slot [G], found [G])."""
    hit = (st.peer_id == pid) & _valid(st) & (pid != 0)
    found = jnp.any(hit, axis=0)
    slot = jnp.argmax(hit, axis=0).astype(I32)
    return slot, found


def _col(arr, slot):
    """arr[slot[g], g] for [P, G] arr.

    One-hot select, NOT take_along_axis: a gather with per-lane
    data-dependent indices costs ~3.3 ms per call at 300k lanes on TPU
    (measured r5 — it dominates the whole slot pass), while the one-hot
    multiply-reduce over the small leading axis is fused elementwise
    work and effectively free."""
    onehot = jnp.arange(arr.shape[0])[:, None] == slot[None, :]
    return jnp.sum(jnp.where(onehot, arr, 0), axis=0)


def _permute0(a, order):
    """a[order[j, g], ..., g] — per-lane permutation along axis 0 via
    one-hot select (see _col: per-lane gathers serialize on TPU).
    ``a`` is [M, G] or [M, E, G]; ``order`` is [M, G]."""
    M = order.shape[0]
    # sel[i, j, g] = (order[j, g] == i)
    sel = order[None, :, :] == jnp.arange(M, dtype=order.dtype)[:, None, None]
    if a.ndim == 2:
        return jnp.sum(jnp.where(sel, a[:, None, :], 0), axis=0)
    # [M, E, G]: broadcast sel over E
    return jnp.sum(
        jnp.where(sel[:, :, None, :], a[:, None, :, :], 0), axis=0
    )


def _set_col(arr, slot, mask, val):
    # one-hot select, NOT arr.at[slot, arange(G)].set(...): a scatter
    # with per-row data-dependent indices lowers to a serial per-row
    # loop on TPU (measured ~100 us/row — it serialized the whole
    # kernel); a [P, G] where() vectorizes
    onehot = jnp.arange(arr.shape[0])[:, None] == slot[None, :]
    val = jnp.broadcast_to(jnp.asarray(val, arr.dtype), slot.shape)
    return jnp.where(onehot & mask, val, arr)


# ---------------------------------------------------------------------------
# log-term ring (internal layout: ring arrays are [W, G])
# ---------------------------------------------------------------------------
def _win_lo(st):
    return jnp.maximum(st.first_index, st.last_index - (_W(st) - 1))


def _ring_at(st, idx):
    wm = _W(st) - 1
    safe = jnp.clip(idx, 0, None) & wm
    return _col(st.ring_term, safe), _col(st.ring_cc, safe)


def _log_term(st, idx):
    """term(idx) -> (term, known, needs_escalation).

    oracle: EntryLog.term.  known=False + esc=False means "definitely
    unavailable" (idx beyond last, a legitimate mismatch); esc=True means
    the ring cannot answer (compacted / outside the W window).
    """
    rt, _ = _ring_at(st, idx)
    zero = idx == 0
    boundary = idx == st.first_index - 1
    in_win = (idx >= _win_lo(st)) & (idx <= st.last_index)
    beyond = idx > st.last_index
    term = jnp.where(zero, 0, jnp.where(boundary, st.base_term, rt))
    known = zero | boundary | in_win
    esc = ~known & ~beyond
    return term, known, esc


def _match_term(st, idx, term):
    """oracle: EntryLog.match_term (False on compacted/unavailable)."""
    t, known, esc = _log_term(st, idx)
    return known & (t == term), esc


def _last_term(st):
    t, _, esc = _log_term(st, st.last_index)
    return t, esc


def _ring_append_one(st, mask, idx, term, cc):
    """Write (term, cc) for log position idx where mask.  One-hot
    select over W (see _set_col: data-dependent scatter serializes)."""
    wm = _W(st) - 1
    pos = jnp.clip(idx, 0, None) & wm
    sel = (jnp.arange(_W(st))[:, None] == pos[None, :]) & mask
    term = jnp.broadcast_to(jnp.asarray(term, st.ring_term.dtype), pos.shape)
    cc = jnp.broadcast_to(jnp.asarray(cc, st.ring_cc.dtype), pos.shape)
    rt = jnp.where(sel, term, st.ring_term)
    rc = jnp.where(sel, cc, st.ring_cc)
    return st._replace(ring_term=rt, ring_cc=rc)


def _pending_cc_scan(st, mask):
    """Any config-change bit in (committed, last_index]?  Used by
    become_leader (oracle: _compute_pending_config_change).  Escalates if
    the uncommitted tail extends below the ring window."""
    W = _W(st)
    idxs = jnp.arange(W)[:, None]  # ring positions, [W, 1]
    # log index currently stored at ring position j:
    # the ring holds indexes in [win_lo, last]; position j holds the unique
    # index in that range congruent to j mod W.
    lo = _win_lo(st)[None, :]
    last = st.last_index[None, :]
    cand = lo + ((idxs - lo) & (W - 1))
    in_tail = (cand > st.committed[None, :]) & (cand <= last)
    any_cc = jnp.any(in_tail & (st.ring_cc == 1), axis=0)
    esc = mask & (st.committed + 1 < _win_lo(st)) & (st.committed < st.last_index)
    return any_cc, esc


# ---------------------------------------------------------------------------
# outbox emission (internal layout: buf is [O, N_FIELDS, G])
# ---------------------------------------------------------------------------
def _emit(
    out: DeviceOut,
    mask,
    *,
    mtype,
    to,
    term,
    log_term=0,
    log_index=0,
    commit=0,
    reject=0,
    hint=0,
    hint_high=0,
    n_entries=0,
    src_slot=-1,
) -> DeviceOut:
    """Append one message per masked row (oracle: Raft._send)."""
    O, G = out.buf.shape[0], out.buf.shape[2]

    def bc(v):
        return jnp.broadcast_to(jnp.asarray(v, I32), (G,))

    row = jnp.stack(
        [
            bc(mtype),
            bc(to),
            bc(term),
            bc(log_term),
            bc(log_index),
            bc(commit),
            bc(reject),
            bc(hint),
            bc(hint_high),
            bc(n_entries),
            bc(src_slot),
        ],
        axis=0,
    )  # [N_FIELDS, G]
    idx = out.count
    can = mask & (idx < O)
    overflow = mask & (idx >= O)
    pos = jnp.clip(idx, 0, O - 1)
    # one-hot select over O (see _set_col: scatter serializes)
    sel = (jnp.arange(O)[:, None] == pos[None, :]) & can  # [O, G]
    buf = jnp.where(sel[:, None, :], row[None, :, :], out.buf)
    return out._replace(
        buf=buf,
        count=out.count + can.astype(I32),
        escalate=out.escalate | jnp.where(overflow, ESC_OVERFLOW, 0),
    )


# ---------------------------------------------------------------------------
# role transitions (oracle: Raft._reset / become_*)
# ---------------------------------------------------------------------------
def _reset(st: DeviceState, mask, new_term) -> DeviceState:
    term_changed = mask & (st.term != new_term)
    st = st._replace(
        term=_w(mask, new_term, st.term),
        vote=_w(term_changed, 0, st.vote),
        leader_id=_w(mask, 0, st.leader_id),
        election_tick=_w(mask, 0, st.election_tick),
        heartbeat_tick=_w(mask, 0, st.heartbeat_tick),
        granted=_w(mask, 0, st.granted),
        transfer_target=_w(mask, 0, st.transfer_target),
        pending_cc=_w(mask, 0, st.pending_cc),
    )
    st = reset_timeout(st, mask)
    # remotes: rm.reset(last+1); self slot keeps match=last
    mgp = mask & _valid(st)
    is_self = (
        jnp.arange(_P(st))[:, None] == st.self_slot[None, :]
    ) & mgp
    last = st.last_index[None, :]
    return st._replace(
        match=_wp(mgp, jnp.where(is_self, last, 0), st.match),
        next_idx=_wp(mgp, last + 1, st.next_idx),
        rstate=_wp(mgp, RS_RETRY, st.rstate),
        snap_index=_wp(mgp, 0, st.snap_index),
    )


def _become_follower(st, mask, new_term, leader) -> DeviceState:
    sk = _self_kind(st)
    role = jnp.where(
        sk == KIND_NON_VOTING,
        ROLE_NON_VOTING,
        jnp.where(sk == KIND_WITNESS, ROLE_WITNESS, ROLE_FOLLOWER),
    )
    st = st._replace(role=_w(mask, role, st.role))
    st = _reset(st, mask, jnp.broadcast_to(jnp.asarray(new_term, I32), (st.G,)))
    return st._replace(leader_id=_w(mask, leader, st.leader_id))


def _become_pre_candidate(st, mask) -> DeviceState:
    """oracle: become_pre_candidate — does NOT touch term/vote/remotes."""
    st = st._replace(
        role=_w(mask, ROLE_PRE_CANDIDATE, st.role),
        granted=_w(mask, 0, st.granted),
        leader_id=_w(mask, 0, st.leader_id),
        election_tick=_w(mask, 0, st.election_tick),
    )
    return reset_timeout(st, mask)


def _become_candidate(st, mask) -> DeviceState:
    st = st._replace(role=_w(mask, ROLE_CANDIDATE, st.role))
    st = _reset(st, mask, st.term + 1)
    st = st._replace(vote=_w(mask, st.replica_id, st.vote))
    return st._replace(granted=_grant_self(st, mask))


def _grant_self(st, mask):
    sel = (
        jnp.arange(st.granted.shape[0])[:, None] == st.self_slot[None, :]
    ) & mask
    return jnp.where(sel, 1, st.granted)


def _vote_quorum(st):
    n = jnp.sum(_voters(st) & (st.granted == 1), axis=0).astype(I32)
    return n >= _quorum(st)


def _vote_rejected(st):
    n = jnp.sum(_voters(st) & (st.granted == 2), axis=0).astype(I32)
    return n >= _quorum(st)


def _append_one(st, out, mask, cc) -> Tuple[DeviceState, DeviceOut]:
    """Leader-side append of one entry at the current term
    (oracle: _append_entries for a single entry, incl. self try_update)."""
    new_last = st.last_index + 1
    out = out._replace(
        append_lo=jnp.where(
            mask, jnp.minimum(out.append_lo, new_last), out.append_lo
        )
    )
    st = _ring_append_one(st, mask, new_last, st.term, cc)
    st = st._replace(last_index=_w(mask, new_last, st.last_index))
    self_match = _col(st.match, st.self_slot)
    self_next = _col(st.next_idx, st.self_slot)
    st = st._replace(
        match=_set_col(
            st.match, st.self_slot, mask, jnp.maximum(self_match, new_last)
        ),
        next_idx=_set_col(
            st.next_idx, st.self_slot, mask, jnp.maximum(self_next, new_last + 1)
        ),
    )
    return st, out


def _try_commit(st, out, mask) -> Tuple[DeviceState, DeviceOut, jnp.ndarray]:
    """oracle: try_commit — sorted-match quorum + current-term-only gate."""
    voters = _voters(st)
    eff = jnp.where(voters, st.match, -1)
    s = jnp.sort(eff, axis=0)  # ascending; non-voters sink to the top
    q = _quorum(st)
    qidx = _col(s, _P(st) - q)
    higher = mask & (qidx > st.committed)
    ok, esc = _match_term(st, qidx, st.term)
    out = out._replace(
        escalate=out.escalate | jnp.where(higher & esc, ESC_WINDOW, 0)
    )
    adv = higher & ok
    st = st._replace(committed=_w(adv, qidx, st.committed))
    return st, out, adv


# ---------------------------------------------------------------------------
# sending replicate / heartbeats
# ---------------------------------------------------------------------------
def _send_replicate(st, out, mask, slot, E) -> Tuple[DeviceState, DeviceOut]:
    """oracle: send_replicate(to) with the device entry cap E.

    ``slot`` is a per-row peer-slot index [G].
    """
    rs = _col(st.rstate, slot)
    nxt = _col(st.next_idx, slot)
    to = _col(st.peer_id, slot)
    paused = (rs == RS_WAIT) | (rs == RS_SNAPSHOT)
    m = mask & ~paused & (to != 0)
    prev = nxt - 1
    # compacted below the resolvable boundary -> snapshot path
    need_ss = m & (prev < st.first_index - 1)
    sel = (
        jnp.arange(out.need_snapshot.shape[0])[:, None] == slot[None, :]
    ) & need_ss
    out = out._replace(
        need_snapshot=jnp.where(sel, 1, out.need_snapshot)
    )
    # hold the remote paused until the host starts the snapshot stream
    st = st._replace(rstate=_set_col(st.rstate, slot, need_ss, RS_WAIT))
    prev_term, known, _esc = _log_term(st, prev)  # esc unused: see below
    m2 = m & ~need_ss
    # below-ring prev (known=False): emit anyway with log_term=0 as a
    # HOST-FIXUP marker — the route host-carries any REPLICATE whose
    # entries predate the ring, and _attach_messages stamps the true
    # prev term + payload from the authoritative scalar log (terms
    # start at 1, so 0 is unambiguous; n>0 is guaranteed here since
    # prev == last is always ring-resident).  Escalating instead
    # livelocked: the reject that walked next below the ring arrived
    # via the ROUTED region, and escalation discards routed inputs —
    # probe -> reject -> escalate forever while a healed follower
    # starved (r4 colocated chaos finding).  The oracle always sends
    # from the full log; this matches it.
    n = jnp.clip(st.last_index - prev, 0, E)
    out = _emit(
        out,
        m2,
        mtype=MT_REPLICATE,
        to=to,
        term=st.term,
        log_index=prev,
        log_term=jnp.where(known, prev_term, 0),
        commit=st.committed,
        n_entries=n,
    )
    # oracle: rm.progress(last sent) only when entries were carried
    prog = m2 & (n > 0)
    last_sent = prev + n
    st = st._replace(
        next_idx=_set_col(
            st.next_idx, slot, prog & (rs == RS_REPLICATE), last_sent + 1
        ),
        rstate=_set_col(st.rstate, slot, prog & (rs == RS_RETRY), RS_WAIT),
    )
    return st, out


def _broadcast_replicate(st, out, mask, E) -> Tuple[DeviceState, DeviceOut]:
    for p in range(_P(st)):
        slot = jnp.full((st.G,), p, I32)
        pm = mask & _valid(st)[p] & (st.self_slot != p)
        st, out = _send_replicate(st, out, pm, slot, E)
    return st, out


def _broadcast_heartbeat(st, out, mask, hint=0, hint_high=0) -> DeviceOut:
    """oracle: broadcast_heartbeat.  ``hint``/``hint_high`` carry a
    pending read-index ctx ([G] or scalar): tick slots get the host's
    latest pending ctx, READ_INDEX slots their own (the device
    ReadIndex hot path — see engine)."""
    for p in range(_P(st)):
        pm = mask & _valid(st)[p] & (st.self_slot != p)
        out = _emit(
            out,
            pm,
            mtype=MT_HEARTBEAT,
            to=st.peer_id[p],
            term=st.term,
            commit=jnp.minimum(st.match[p], st.committed),
            # uncapped commit advisory for the follower's
            # leader_commit_hint (oracle: broadcast_heartbeat's
            # log_index; unused by HEARTBEAT handling proper)
            log_index=st.committed,
            hint=hint,
            hint_high=hint_high,
        )
    return out


def _become_leader(st, out, mask, E) -> Tuple[DeviceState, DeviceOut]:
    """oracle: become_leader (+ the single-voter fast commit)."""
    st = st._replace(role=_w(mask, ROLE_LEADER, st.role))
    st = _reset(st, mask, st.term)
    st = st._replace(leader_id=_w(mask, st.replica_id, st.leader_id))
    # full activity window for a fresh leader (oracle + etcd-raft's
    # RecentActive=true at becomeLeader): with fused ticks an election
    # window can elapse in two launches — one ack round-trip — and the
    # first CheckQuorum against empty lanes deposed every winner.
    # Bit 0 only: a fresh leader's first lease anchor is a real quorum
    # of answers, never this fabricated window
    st = st._replace(
        active=_wp(mask & _valid(st), ACTIVE_LIVE, st.active)
    )
    any_cc, esc = _pending_cc_scan(st, mask)
    out = out._replace(escalate=out.escalate | jnp.where(esc, ESC_WINDOW, 0))
    st = st._replace(
        pending_cc=_w(mask, any_cc.astype(I32), st.pending_cc)
    )
    # commit barrier: empty entry at the new term
    st, out = _append_one(st, out, mask, jnp.zeros((st.G,), I32))
    # record the barrier so the host can stamp it empty during append
    # reconstruction even if this row steps down LATER IN THE SAME STEP
    # (a higher-term message after the win) — the barrier is the only
    # append that never has a staged or wire payload
    out = out._replace(
        barrier_idx=jnp.where(mask, st.last_index, out.barrier_idx),
        barrier_term=jnp.where(mask, st.term, out.barrier_term),
    )
    single = _num_voters(st) == 1
    st, out, _ = _try_commit(st, out, mask & single & _self_is_voter(st))
    return st, out


# ---------------------------------------------------------------------------
# campaign (oracle: campaign / _handle_election)
#
# HANDLER INVARIANT (load-bearing for the _process_slot lax.cond gating):
# every handler below — and every handler added later — must be a PURE
# NO-OP under an all-false mask: all writes to ``st``/``out`` must be
# mask-selected (jnp.where/_emit with the handler's mask), with NO
# unmasked state normalization, clamping or counter maintenance outside
# the mask.  _process_slot skips whole handler blocks via lax.cond when
# a slot batch contains none of their message types; a handler that
# mutated anything under an all-false mask would make gated and ungated
# execution diverge, surfacing only as rare batch-composition-dependent
# corruption.  tests/test_kernel_parity.py pins the equivalence by
# running _process_slot with every gate forced open (_FORCE_GATES)
# against the normally-gated path.
# ---------------------------------------------------------------------------
def _campaign(st, out, mask, pre, transfer, E) -> Tuple[DeviceState, DeviceOut]:
    pre_m = mask & pre
    real_m = mask & ~pre
    # --- prevote leg ---------------------------------------------------
    st = _become_pre_candidate(st, pre_m)
    st = st._replace(granted=_grant_self(st, pre_m))
    promote = pre_m & _vote_quorum(st)  # single-voter shortcut
    bcast_pre = pre_m & ~promote
    lt, lt_esc = _last_term(st)
    out = out._replace(
        escalate=out.escalate | jnp.where(bcast_pre & lt_esc, ESC_WINDOW, 0)
    )
    for p in range(_P(st)):
        pm = (
            bcast_pre
            & _voters(st)[p]
            & (st.self_slot != p)
        )
        out = _emit(
            out,
            pm,
            mtype=MT_REQUEST_PREVOTE,
            to=st.peer_id[p],
            term=st.term + 1,
            log_index=st.last_index,
            log_term=lt,
        )
    real_m = real_m | promote
    # --- real leg ------------------------------------------------------
    st = _become_candidate(st, real_m)
    lead = real_m & _vote_quorum(st)  # single voter
    st, out = _become_leader(st, out, lead, E)
    bcast = real_m & ~lead
    lt2, lt2_esc = _last_term(st)
    out = out._replace(
        escalate=out.escalate | jnp.where(bcast & lt2_esc, ESC_WINDOW, 0)
    )
    hint = jnp.where(transfer, st.replica_id, 0)
    for p in range(_P(st)):
        pm = bcast & _voters(st)[p] & (st.self_slot != p)
        out = _emit(
            out,
            pm,
            mtype=MT_REQUEST_VOTE,
            to=st.peer_id[p],
            term=st.term,
            log_index=st.last_index,
            log_term=lt2,
            hint=hint,
        )
    return st, out


def _handle_election(st, out, mask, hint, E):
    """oracle: _handle_election."""
    m = (
        mask
        & (st.role != ROLE_LEADER)
        & (st.role != ROLE_NON_VOTING)
        & (st.role != ROLE_WITNESS)
        & _self_is_voter(st)
    )
    transfer = hint == st.replica_id
    pre = (st.pre_vote == 1) & ~transfer
    return _campaign(st, out, m, pre, transfer, E)


# ---------------------------------------------------------------------------
# check quorum (oracle: _handle_check_quorum)
# ---------------------------------------------------------------------------
def _check_quorum(st, mask) -> DeviceState:
    voters = _voters(st)
    is_self = jnp.arange(_P(st))[:, None] == st.self_slot[None, :]
    live = (st.active & ACTIVE_LIVE) != 0
    cnt = 1 + jnp.sum(voters & ~is_self & live, axis=0).astype(I32)
    # the sweep clears bit 0 alone: bit 1 is the tick feed's to clear
    st = st._replace(
        active=_wp(mask & voters, st.active & ACTIVE_FRESH, st.active)
    )
    down = mask & (cnt < _quorum(st))
    return _become_follower(st, down, st.term, 0)


# ---------------------------------------------------------------------------
# tick (oracle: Raft.tick)
# ---------------------------------------------------------------------------
def _tick(
    st, out, mask, E, hint=0, hint_high=0, n=None
) -> Tuple[DeviceState, DeviceOut]:
    """Advance the tick timers by ``n`` logical ticks in one slot
    (multi-tick fusion).

    ``n=1`` is bit-identical to the reference's per-tick stepping; the
    fused form exists because one launch over all rows costs the same
    whether a slot carries 1 tick or 10, and election timeouts are tens
    of ticks.  Encoders cap ``n`` at election_timeout//2 (the same cap
    the scalar step applies to drained tick batches), so at most ONE
    timer threshold crossing happens per slot.  Heartbeats coalesce: k
    firings within the fused span emit one broadcast — the reference
    coalesces heartbeat bursts the same way [U], and a follower only
    needs >=1 heartbeat per election window to hold its timer."""
    if n is None:
        n = jnp.ones((st.G,), I32)
    lead = mask & (st.role == ROLE_LEADER)
    non = mask & (st.role != ROLE_LEADER)
    # --- leader tick ---------------------------------------------------
    # "answered since this row's ticks were fed" starts over: a launch
    # feeds a row's ticks as ONE slot, the last of its host region, so
    # only the answers the later rounds route back set bit 1 again
    st = st._replace(
        active=_wp(lead[None, :], st.active & ACTIVE_LIVE, st.active)
    )
    el = st.election_tick + n
    hb = st.heartbeat_tick + n
    fired = el >= st.election_timeout
    st = st._replace(
        election_tick=_w(lead, jnp.where(fired, 0, el), st.election_tick),
        heartbeat_tick=_w(lead, hb, st.heartbeat_tick),
    )
    cq = lead & fired & (st.check_quorum == 1)
    st = _check_quorum(st, cq)
    still = lead & (st.role == ROLE_LEADER)
    st = st._replace(
        transfer_target=_w(still & fired, 0, st.transfer_target)
    )
    hb_fire = still & (st.heartbeat_tick >= st.heartbeat_timeout)
    st = st._replace(heartbeat_tick=_w(hb_fire, 0, st.heartbeat_tick))
    out = _broadcast_heartbeat(st, out, hb_fire, hint, hint_high)
    # --- non-leader tick ----------------------------------------------
    el2 = st.election_tick + n
    time_up = el2 >= st.rand_timeout
    nvw = (st.role == ROLE_NON_VOTING) | (st.role == ROLE_WITNESS)
    probe = non & nvw & (st.check_quorum == 1) & time_up
    st = st._replace(election_tick=_w(non, el2, st.election_tick))
    st = st._replace(election_tick=_w(probe, 0, st.election_tick))
    st = reset_timeout(st, probe)
    elect = non & ~nvw & time_up
    st = st._replace(election_tick=_w(elect, 0, st.election_tick))
    st, out = _handle_election(st, out, elect, jnp.zeros((st.G,), I32), E)
    return st, out


# ---------------------------------------------------------------------------
# message-term gate (oracle: _on_message_term)
# ---------------------------------------------------------------------------
def _on_message_term(st, out, msg, mask):
    mt = msg["mtype"]
    mterm = msg["term"]
    local = mterm == 0
    higher = mask & ~local & (mterm > st.term)
    lower = mask & ~local & (mterm < st.term)
    vote_like = (mt == MT_REQUEST_VOTE) | (mt == MT_REQUEST_PREVOTE)
    in_lease = (
        (st.check_quorum == 1)
        & (st.leader_id != 0)
        & (st.election_tick < st.election_timeout)
    )
    drop_lease = higher & vote_like & in_lease & (msg["hint"] == 0)
    leader_msg = (
        (mt == MT_REPLICATE)
        | (mt == MT_INSTALL_SNAPSHOT)
        | (mt == MT_HEARTBEAT)
        | (mt == MT_TIMEOUT_NOW)
        | (mt == MT_READ_INDEX_RESP)
    )
    keep_term = (mt == MT_REQUEST_PREVOTE) | (
        (mt == MT_REQUEST_PREVOTE_RESP) & (msg["reject"] == 0)
    )
    become = higher & ~drop_lease & ~keep_term
    st = _become_follower(
        st, become, mterm, jnp.where(leader_msg, msg["from_id"], 0)
    )
    # deposed-leader poke: a lower-term leader must step down
    poke = (
        lower
        & ((mt == MT_REPLICATE) | (mt == MT_HEARTBEAT) | (mt == MT_INSTALL_SNAPSHOT))
        & ((st.check_quorum == 1) | (st.pre_vote == 1))
    )
    out = _emit(
        out, poke, mtype=MT_REPLICATE_RESP, to=msg["from_id"], term=st.term
    )
    pv_rej = lower & (mt == MT_REQUEST_PREVOTE)
    out = _emit(
        out,
        pv_rej,
        mtype=MT_REQUEST_PREVOTE_RESP,
        to=msg["from_id"],
        term=st.term,
        reject=1,
    )
    passed = mask & (local | (mterm == st.term) | (higher & ~drop_lease))
    return st, out, passed


# ---------------------------------------------------------------------------
# vote handling
# ---------------------------------------------------------------------------
def _can_grant_vote(st, msg, prevote):
    return (
        (st.vote == 0)
        | (st.vote == msg["from_id"])
        | (prevote & (msg["term"] > st.term))
    )


def _up_to_date(st, out, mask, msg):
    lt, esc = _last_term(st)
    out = out._replace(
        escalate=out.escalate | jnp.where(mask & esc, ESC_WINDOW, 0)
    )
    utd = (msg["log_term"] > lt) | (
        (msg["log_term"] == lt) & (msg["log_index"] >= st.last_index)
    )
    return out, utd


def _handle_request_vote(st, out, msg, mask):
    m = mask & (st.role != ROLE_NON_VOTING)
    out, utd = _up_to_date(st, out, m, msg)
    grant = m & _can_grant_vote(st, msg, jnp.asarray(False)) & utd
    st = st._replace(
        election_tick=_w(grant, 0, st.election_tick),
        vote=_w(grant, msg["from_id"], st.vote),
    )
    out = _emit(
        out,
        m,
        mtype=MT_REQUEST_VOTE_RESP,
        to=msg["from_id"],
        term=st.term,
        reject=jnp.where(grant, 0, 1),
    )
    return st, out


def _handle_request_prevote(st, out, msg, mask):
    m = mask & (st.role != ROLE_NON_VOTING)
    out, utd = _up_to_date(st, out, m, msg)
    grant = m & utd & (
        (msg["term"] > st.term) | _can_grant_vote(st, msg, jnp.asarray(True))
    )
    out = _emit(
        out,
        m,
        mtype=MT_REQUEST_PREVOTE_RESP,
        to=msg["from_id"],
        term=jnp.where(grant, msg["term"], st.term),
        reject=jnp.where(grant, 0, 1),
    )
    return st, out


# ---------------------------------------------------------------------------
# replicate / heartbeat handling (follower side)
# ---------------------------------------------------------------------------
def _handle_replicate(st, out, msg, mask, slot_i):
    """oracle: _handle_replicate (follower log append + log matching)."""
    E = int(msg["ent_term"].shape[0])
    stale = mask & (msg["log_index"] < st.committed)
    out = _emit(
        out,
        stale,
        mtype=MT_REPLICATE_RESP,
        to=msg["from_id"],
        term=st.term,
        log_index=st.committed,
    )
    m = mask & ~stale
    prev_ok, esc = _match_term(st, msg["log_index"], msg["log_term"])
    out = out._replace(
        escalate=out.escalate | jnp.where(m & esc, ESC_WINDOW, 0)
    )
    ok = m & prev_ok
    n = msg["n_entries"]
    last_new = msg["log_index"] + n
    # conflict scan: first carried entry whose (index, term) mismatches
    conflict_off = jnp.full((st.G,), E + 1, I32)
    conflict_esc = jnp.zeros((st.G,), bool)
    for i in reversed(range(E)):
        idx = msg["log_index"] + 1 + i
        et = msg["ent_term"][i]
        mt_ok, e_esc = _match_term(st, idx, et)
        has = ok & (i < n)
        conflict_off = jnp.where(has & ~mt_ok, i, conflict_off)
        conflict_esc = jnp.where(has & ~mt_ok, e_esc, conflict_esc)
    # a conflict beyond last_index is an append, not an escalation
    idx_at_conf = msg["log_index"] + 1 + conflict_off
    conflict_esc = conflict_esc & (idx_at_conf <= st.last_index)
    out = out._replace(
        escalate=out.escalate | jnp.where(ok & conflict_esc, ESC_WINDOW, 0)
    )
    has_conflict = ok & (conflict_off <= E)
    # invariant: conflict must be above commit (oracle raises otherwise)
    bad = has_conflict & (idx_at_conf <= st.committed)
    out = out._replace(
        escalate=out.escalate | jnp.where(bad, ESC_INVARIANT, 0)
    )
    # append entries[conflict_off:] — ring writes + truncation to last_new
    first_written = msg["log_index"] + 1 + conflict_off
    out = out._replace(
        append_lo=jnp.where(
            has_conflict,
            jnp.minimum(out.append_lo, first_written),
            out.append_lo,
        )
    )
    for i in range(E):
        idx = msg["log_index"] + 1 + i
        wmask = has_conflict & (i >= conflict_off) & (i < n)
        st = _ring_append_one(
            st, wmask, idx, msg["ent_term"][i], msg["ent_cc"][i]
        )
    st = st._replace(
        last_index=_w(has_conflict, last_new, st.last_index)
    )
    # commit_to(min(m.commit, last_new))
    new_commit = jnp.minimum(msg["commit"], last_new)
    st = st._replace(
        committed=_w(ok, jnp.maximum(st.committed, new_commit), st.committed)
    )
    out = _emit(
        out,
        ok,
        mtype=MT_REPLICATE_RESP,
        to=msg["from_id"],
        term=st.term,
        log_index=last_new,
    )
    rej = m & ~prev_ok
    out = _emit(
        out,
        rej,
        mtype=MT_REPLICATE_RESP,
        to=msg["from_id"],
        term=st.term,
        reject=1,
        log_index=msg["log_index"],
        hint=st.last_index,
    )
    return st, out


def _handle_heartbeat(st, out, msg, mask):
    new_commit = jnp.minimum(msg["commit"], st.last_index)
    st = st._replace(
        committed=_w(mask, jnp.maximum(st.committed, new_commit), st.committed)
    )
    out = _emit(
        out,
        mask,
        mtype=MT_HEARTBEAT_RESP,
        to=msg["from_id"],
        term=st.term,
        hint=msg["hint"],
        hint_high=msg["hint_high"],
    )
    return st, out


# ---------------------------------------------------------------------------
# leader-side response handling
# ---------------------------------------------------------------------------
def _handle_replicate_resp(st, out, msg, mask, E):
    slot, found = _slot_of(st, msg["from_id"])
    m = mask & found
    st = st._replace(
        active=_set_col(st.active, slot, m, ACTIVE_LIVE | ACTIVE_FRESH)
    )
    rs = _col(st.rstate, slot)
    match = _col(st.match, slot)
    nxt = _col(st.next_idx, slot)
    snap = _col(st.snap_index, slot)
    rej = m & (msg["reject"] == 1)
    # -- decrease (oracle: remote.decrease) -----------------------------
    repl = rs == RS_REPLICATE
    do_r = rej & repl & (msg["log_index"] > match)
    # become_retry from REPLICATE: next = match + 1
    st = st._replace(
        next_idx=_set_col(st.next_idx, slot, do_r, match + 1),
        snap_index=_set_col(st.snap_index, slot, do_r, 0),
        rstate=_set_col(st.rstate, slot, do_r, RS_RETRY),
    )
    do_nr = rej & ~repl & (nxt - 1 == msg["log_index"])
    dec_next = jnp.maximum(
        jnp.maximum(jnp.minimum(msg["log_index"], msg["hint"] + 1), match + 1),
        1,
    )
    st = st._replace(
        next_idx=_set_col(st.next_idx, slot, do_nr, dec_next),
        rstate=_set_col(
            st.rstate,
            slot,
            do_nr & (rs == RS_WAIT),
            RS_RETRY,
        ),
    )
    st, out = _send_replicate(st, out, do_r | do_nr, slot, E)
    # -- ack (oracle: _handle_replicate_resp accept path) ---------------
    ack = m & (msg["reject"] == 0)
    paused = (rs == RS_WAIT) | (rs == RS_SNAPSHOT)
    advanced = ack & (match < msg["log_index"])
    new_match = jnp.maximum(match, msg["log_index"])
    new_next = jnp.maximum(nxt, msg["log_index"] + 1)
    st = st._replace(
        match=_set_col(st.match, slot, advanced, new_match),
        next_idx=_set_col(st.next_idx, slot, ack, new_next),
        rstate=_set_col(
            st.rstate, slot, advanced & (rs == RS_WAIT), RS_RETRY
        ),
    )
    # snapshot -> retry -> replicate promotions
    rs2 = _col(st.rstate, slot)
    promote_ss = advanced & (rs2 == RS_SNAPSHOT) & (new_match >= snap)
    st = st._replace(
        next_idx=_set_col(
            st.next_idx,
            slot,
            promote_ss,
            jnp.maximum(new_match + 1, snap + 1),
        ),
        snap_index=_set_col(st.snap_index, slot, promote_ss, 0),
        rstate=_set_col(st.rstate, slot, promote_ss, RS_RETRY),
    )
    rs3 = _col(st.rstate, slot)
    promote_r = advanced & (rs3 == RS_RETRY)
    st = st._replace(
        next_idx=_set_col(st.next_idx, slot, promote_r, new_match + 1),
        snap_index=_set_col(st.snap_index, slot, promote_r, 0),
        rstate=_set_col(st.rstate, slot, promote_r, RS_REPLICATE),
    )
    st, out, committed_adv = _try_commit(st, out, advanced)
    st, out = _broadcast_replicate(st, out, committed_adv, E)
    st, out = _send_replicate(
        st, out, advanced & ~committed_adv & paused, slot, E
    )
    # leader transfer: target caught up -> TIMEOUT_NOW
    ready = (
        advanced
        & (st.transfer_target == msg["from_id"])
        & (st.last_index == new_match)
    )
    out = _emit(
        out, ready, mtype=MT_TIMEOUT_NOW, to=msg["from_id"], term=st.term
    )
    # stale ack while streaming a snapshot that has completed
    rs4 = _col(st.rstate, slot)
    m4 = _col(st.match, slot)
    s4 = _col(st.snap_index, slot)
    stale_ss = ack & ~advanced & (rs4 == RS_SNAPSHOT) & (m4 >= s4)
    st = st._replace(
        next_idx=_set_col(
            st.next_idx, slot, stale_ss, jnp.maximum(m4 + 1, s4 + 1)
        ),
        snap_index=_set_col(st.snap_index, slot, stale_ss, 0),
        rstate=_set_col(st.rstate, slot, stale_ss, RS_RETRY),
    )
    return st, out


def _handle_heartbeat_resp(st, out, msg, mask, E):
    slot, found = _slot_of(st, msg["from_id"])
    m = mask & found
    st = st._replace(
        active=_set_col(st.active, slot, m, ACTIVE_LIVE | ACTIVE_FRESH)
    )
    rs = _col(st.rstate, slot)
    st = st._replace(
        rstate=_set_col(st.rstate, slot, m & (rs == RS_WAIT), RS_RETRY)
    )
    lag = m & (_col(st.match, slot) < st.last_index)
    st, out = _send_replicate(st, out, lag, slot, E)
    # read-index ctx echo: surface the confirmation to the HOST as a
    # synthetic READ_INDEX_RESP-to-self (log_index = confirming voter;
    # the engine routes self-addressed resps to node.device_reads).
    # Only VOTING members count — matching the oracle's quorum gate.
    kind = _col(st.peer_kind, slot)
    voter = (kind == KIND_VOTER) | (kind == KIND_WITNESS)
    has_ctx = m & voter & ((msg["hint"] != 0) | (msg["hint_high"] != 0))
    out = _emit(
        out,
        has_ctx,
        mtype=MT_READ_INDEX_RESP,
        to=st.replica_id,
        term=st.term,
        log_index=msg["from_id"],
        hint=msg["hint"],
        hint_high=msg["hint_high"],
    )
    return st, out


def _handle_read_index(st, out, msg, mask) -> DeviceOut:
    """Device ReadIndex hot path (oracle: _handle_leader_read_index).

    The ctx -> (index, acks) table lives on the HOST (node.device_reads);
    the kernel only emits synthetic READ_INDEX_RESP-to-self messages the
    engine intercepts:

        reject=1                     -> drop the pending read (not
                                        leader / current-term gate)
        reject=0, log_index=0        -> request recorded at index=commit
        reject=0, log_index=K>0      -> confirmation from voter K
                                        (emitted by heartbeat-resp)

    and broadcasts the quorum-confirming heartbeats with the ctx riding
    the hint fields — so a read-heavy workload stays device-resident.
    """
    lead = mask & (st.role == ROLE_LEADER) & (_self_kind(st) != KIND_WITNESS)
    non_lead = mask & ~lead
    out = _emit(
        out,
        non_lead,
        mtype=MT_READ_INDEX_RESP,
        to=st.replica_id,
        term=st.term,
        reject=1,
        hint=msg["hint"],
        hint_high=msg["hint_high"],
    )
    # oracle: committed_entry_in_current_term — unsafe to serve before
    # the leader's no-op barrier commits
    ok, esc = _match_term(st, st.committed, st.term)
    out = out._replace(
        escalate=out.escalate | jnp.where(lead & esc, ESC_WINDOW, 0)
    )
    gate_fail = lead & ~ok & ~esc
    out = _emit(
        out,
        gate_fail,
        mtype=MT_READ_INDEX_RESP,
        to=st.replica_id,
        term=st.term,
        reject=1,
        hint=msg["hint"],
        hint_high=msg["hint_high"],
    )
    serve = lead & ok
    out = _emit(
        out,
        serve,
        mtype=MT_READ_INDEX_RESP,
        to=st.replica_id,
        term=st.term,
        commit=st.committed,
        hint=msg["hint"],
        hint_high=msg["hint_high"],
    )
    # single-voter groups confirm instantly host-side (quorum == 1)
    multi = serve & (_num_voters(st) > 1)
    return _broadcast_heartbeat(st, out, multi, msg["hint"], msg["hint_high"])


def _handle_unreachable(st, msg, mask):
    slot, found = _slot_of(st, msg["from_id"])
    m = mask & found & (_col(st.rstate, slot) == RS_REPLICATE)
    match = _col(st.match, slot)
    st = st._replace(
        next_idx=_set_col(st.next_idx, slot, m, match + 1),
        snap_index=_set_col(st.snap_index, slot, m, 0),
        rstate=_set_col(st.rstate, slot, m, RS_RETRY),
    )
    return st


def _handle_snapshot_status(st, msg, mask):
    """oracle: _handle_snapshot_status / _handle_snapshot_received — the
    remote leaves SNAPSHOT into WAIT (become_wait)."""
    slot, found = _slot_of(st, msg["from_id"])
    m = mask & found & (_col(st.rstate, slot) == RS_SNAPSHOT)
    snap = _col(st.snap_index, slot)
    # reject=1 clears the pending snapshot index first (SNAPSHOT_STATUS)
    snap = jnp.where(m & (msg["reject"] == 1), 0, snap)
    match = _col(st.match, slot)
    new_next = jnp.maximum(match + 1, snap + 1)
    st = st._replace(
        next_idx=_set_col(st.next_idx, slot, m, new_next),
        snap_index=_set_col(st.snap_index, slot, m, 0),
        rstate=_set_col(st.rstate, slot, m, RS_WAIT),
    )
    return st


# ---------------------------------------------------------------------------
# leader transfer request (oracle: _handle_leader_transfer)
# ---------------------------------------------------------------------------
def _handle_leader_transfer(st, out, msg, mask, E):
    """A leader's own transfer request, ``hint`` the target: a voter
    other than self, and no transfer in flight, or the request is
    ignored.  The target caught up gets TIMEOUT_NOW at once; otherwise
    it is sent what it lacks, and ``_handle_replicate_resp`` sends
    TIMEOUT_NOW when its answer shows it caught up.  ``mask`` holds
    leader rows only: a replica that does not lead forwards the request
    over the wire, which is the scalar path's (the caller escalates)."""
    target = msg["hint"]
    slot, found = _slot_of(st, target)
    ok = (
        mask
        & found
        & (_col(st.peer_kind, slot) == KIND_VOTER)
        & (target != st.replica_id)
        & (st.transfer_target == 0)
    )
    st = st._replace(
        transfer_target=_w(ok, target, st.transfer_target),
        election_tick=_w(ok, 0, st.election_tick),
    )
    caught_up = ok & (_col(st.match, slot) == st.last_index)
    out = _emit(out, caught_up, mtype=MT_TIMEOUT_NOW, to=target, term=st.term)
    return _send_replicate(st, out, ok & ~caught_up, slot, E)


# ---------------------------------------------------------------------------
# propose (oracle: _handle_propose)
# ---------------------------------------------------------------------------
def _handle_propose(st, out, msg, mask, slot_i, E):
    lead = mask & (st.role == ROLE_LEADER)
    n = msg["n_entries"]
    transferring = st.transfer_target != 0
    drop_all = lead & transferring
    accept = lead & ~transferring
    base = st.last_index
    # per-entry config-change gate, sequential within the message
    appended_any = jnp.zeros((st.G,), bool)
    ent_drop = out.ent_drop
    for i in range(E):
        has = accept & (i < n)
        is_cc = msg["ent_cc"][i] == 1
        dropped = has & is_cc & (st.pending_cc == 1)
        ent_drop = ent_drop.at[slot_i, i].set(
            jnp.where(dropped, 1, ent_drop[slot_i, i])
        )
        put = has & ~dropped
        st = st._replace(
            pending_cc=_w(put & is_cc, 1, st.pending_cc)
        )
        st, out = _append_one(st, out, put, jnp.where(is_cc, 1, 0))
        appended_any = appended_any | put
    out = out._replace(ent_drop=ent_drop)
    # single-voter commit advance happens inside _append_entries via
    # try_commit; mirror it once after the batch (equivalent because the
    # commit quorum for a single voter is just its own last_index)
    single = (_num_voters(st) == 1) & _self_is_voter(st)
    st, out, _ = _try_commit(st, out, appended_any & single)
    st, out = _broadcast_replicate(st, out, appended_any, E)
    # host bookkeeping: where did this slot's entries land?
    sb = jnp.where(
        accept,
        base,
        jnp.where(drop_all, SLOT_DROPPED, out.slot_base[slot_i]),
    )
    stm = jnp.where(accept, st.term, out.slot_term[slot_i])
    # follower: forward to the leader; candidate/no-leader: drop
    foll = mask & (
        (st.role == ROLE_FOLLOWER)
        | (st.role == ROLE_NON_VOTING)
        | (st.role == ROLE_WITNESS)
    )
    fwd = foll & (st.leader_id != 0)
    out = _emit(
        out,
        fwd,
        mtype=MT_PROPOSE,
        to=st.leader_id,
        term=st.term,
        n_entries=n,
        src_slot=slot_i,
    )
    sb = jnp.where(fwd, SLOT_FORWARDED, sb)
    dropped_f = (foll & (st.leader_id == 0)) | (
        mask
        & ((st.role == ROLE_CANDIDATE) | (st.role == ROLE_PRE_CANDIDATE))
    )
    sb = jnp.where(dropped_f, SLOT_DROPPED, sb)
    out = out._replace(
        slot_base=out.slot_base.at[slot_i].set(sb),
        slot_term=out.slot_term.at[slot_i].set(stm),
    )
    return st, out


# ---------------------------------------------------------------------------
# the per-slot dispatcher (oracle: Raft.handle + _step)
# ---------------------------------------------------------------------------
def _is_hot(mt):
    acc = jnp.zeros_like(mt, dtype=bool)
    for t in HOT_TYPES:
        acc = acc | (mt == t)
    return acc


def _process_slot(st, out, msg, slot_i, E):
    """One inbox slot for every row.  INTERNAL layout: state peer/ring
    arrays [P, G]/[W, G], out.buf [O, N_FIELDS, G], msg fields [G]
    (``ent_term``/``ent_cc`` are [E, G]).

    Handler blocks are gated behind ``lax.cond`` on batch-wide presence
    of their message types: a slot pass only pays for the handlers its
    messages actually need (measured r5: a tick-only slot dropped from
    ~12 ms to ~2.3 ms at 300k rows — the untaken branches are real
    runtime skips on TPU, not just masked no-ops).  Reordering handler
    blocks is semantics-preserving because per-row handler masks are
    disjoint by message type; the one real cross-block ordering
    constraint — candidates demoted by a leader's REPLICATE/HEARTBEAT
    must then be processed by the follower block in the same slot — is
    kept (cand block runs before foll block).
    """
    mask = (msg["mtype"] != 0) & (out.escalate == 0)
    mt = msg["mtype"]
    # cold types escalate the whole row
    out = out._replace(
        escalate=out.escalate | jnp.where(mask & ~_is_hot(mt), ESC_COLD, 0)
    )
    mask = mask & _is_hot(mt)

    def _has(*types):
        acc = jnp.zeros((), bool)
        for t in types:
            acc = acc | jnp.any(mask & (mt == t))
        return acc

    def _gate(pred, fn, st, out):
        # _FORCE_GATES (test hook): run every handler regardless of
        # batch presence, exercising them under all-false masks — the
        # parity test's lever for pinning the handler no-op invariant
        # (see the campaign section header)
        if _FORCE_GATES:
            return fn(st, out)
        return lax.cond(pred, fn, lambda s, o: (s, o), st, out)

    # LOCAL_TICK short-circuits the gate (oracle: handle); log_index
    # carries the fused tick count (0 on legacy single-tick slots)
    st, out = _gate(
        _has(MT_TICK),
        lambda s, o: _tick(
            s, o, mask & (mt == MT_TICK), E, msg["hint"], msg["hint_high"],
            n=jnp.maximum(msg["log_index"], 1),
        ),
        st, out,
    )
    rest = mask & (mt != MT_TICK)

    def _non_tick(st, out):
        st, out, passed = _on_message_term(st, out, msg, rest)

        def _votes(st, out):
            st, out = _handle_election(
                st, out, passed & (mt == MT_ELECTION), msg["hint"], E
            )
            st, out = _handle_request_vote(
                st, out, msg, passed & (mt == MT_REQUEST_VOTE)
            )
            st, out = _handle_request_prevote(
                st, out, msg, passed & (mt == MT_REQUEST_PREVOTE)
            )
            return st, out

        st, out = _gate(
            _has(MT_ELECTION, MT_REQUEST_VOTE, MT_REQUEST_PREVOTE),
            _votes, st, out,
        )
        role_routed = passed & ~(
            (mt == MT_ELECTION)
            | (mt == MT_REQUEST_VOTE)
            | (mt == MT_REQUEST_PREVOTE)
        )

        def _prop_read(st, out):
            st, out = _handle_propose(
                st, out, msg, role_routed & (mt == MT_PROPOSE), slot_i, E
            )
            out = _handle_read_index(
                st, out, msg, role_routed & (mt == MT_READ_INDEX)
            )
            return st, out

        st, out = _gate(
            _has(MT_PROPOSE, MT_READ_INDEX), _prop_read, st, out
        )

        def _rare(st, out):
            lead = role_routed & (st.role == ROLE_LEADER)
            # a transfer request on a row that does not lead (the host's
            # mirror was behind) goes back to the scalar path whole
            out = out._replace(escalate=out.escalate | jnp.where(
                role_routed & ~lead & (mt == MT_LEADER_TRANSFER),
                ESC_COLD, 0,
            ))
            st, out = _handle_leader_transfer(
                st, out, msg, lead & (mt == MT_LEADER_TRANSFER), E
            )
            st = _check_quorum(st, lead & (mt == MT_CHECK_QUORUM))
            st = _handle_unreachable(st, msg, lead & (mt == MT_UNREACHABLE))
            st = _handle_snapshot_status(
                st,
                msg,
                lead
                & ((mt == MT_SNAPSHOT_STATUS) | (mt == MT_SNAPSHOT_RECEIVED)),
            )
            return st, out

        st, out = _gate(
            _has(MT_CHECK_QUORUM, MT_UNREACHABLE, MT_SNAPSHOT_STATUS,
                 MT_SNAPSHOT_RECEIVED, MT_LEADER_TRANSFER),
            _rare, st, out,
        )

        def _lead_resps(st, out):
            lead = role_routed & (st.role == ROLE_LEADER)
            st, out = _handle_replicate_resp(
                st, out, msg, lead & (mt == MT_REPLICATE_RESP), E
            )
            st, out = _handle_heartbeat_resp(
                st, out, msg, lead & (mt == MT_HEARTBEAT_RESP), E
            )
            return st, out

        st, out = _gate(
            _has(MT_REPLICATE_RESP, MT_HEARTBEAT_RESP), _lead_resps, st, out
        )

        def _cand(st, out):
            cand = role_routed & (
                (st.role == ROLE_CANDIDATE) | (st.role == ROLE_PRE_CANDIDATE)
            )
            # REPLICATE / HEARTBEAT at our term from a legitimate leader
            from_leader = cand & ((mt == MT_REPLICATE) | (mt == MT_HEARTBEAT))
            st = _become_follower(st, from_leader, st.term, msg["from_id"])
            # vote responses
            vr = cand & (mt == MT_REQUEST_VOTE_RESP) & (
                st.role == ROLE_CANDIDATE
            )
            slot, found = _slot_of(st, msg["from_id"])
            rec = vr & found
            st = st._replace(
                granted=_set_col(
                    st.granted, slot, rec, jnp.where(msg["reject"] == 1, 2, 1)
                )
            )
            win = vr & _vote_quorum(st)
            st, out = _become_leader(st, out, win, E)
            st, out = _broadcast_replicate(st, out, win, E)
            lose = vr & ~win & _vote_rejected(st)
            st = _become_follower(st, lose, st.term, 0)
            pv = cand & (mt == MT_REQUEST_PREVOTE_RESP) & (
                st.role == ROLE_PRE_CANDIDATE
            )
            slot2, found2 = _slot_of(st, msg["from_id"])
            rec2 = pv & found2
            st = st._replace(
                granted=_set_col(
                    st.granted, slot2, rec2, jnp.where(msg["reject"] == 1, 2, 1)
                )
            )
            pv_win = pv & _vote_quorum(st)
            st, out = _campaign(
                st,
                out,
                pv_win,
                jnp.zeros((st.G,), bool),
                jnp.zeros((st.G,), bool),
                E,
            )
            pv_lose = pv & ~pv_win & _vote_rejected(st)
            st = _become_follower(st, pv_lose, st.term, 0)
            return st, out

        st, out = _gate(
            _has(MT_REQUEST_VOTE_RESP, MT_REQUEST_PREVOTE_RESP,
                 MT_REPLICATE, MT_HEARTBEAT),
            _cand, st, out,
        )

        def _foll(st, out):
            # follower-ish roles (+ the just-demoted candidates)
            foll = role_routed & (
                (st.role == ROLE_FOLLOWER)
                | (st.role == ROLE_NON_VOTING)
                | (st.role == ROLE_WITNESS)
            )
            lmsg = foll & ((mt == MT_REPLICATE) | (mt == MT_HEARTBEAT))
            st = st._replace(
                election_tick=_w(lmsg, 0, st.election_tick),
                leader_id=_w(lmsg, msg["from_id"], st.leader_id),
            )
            st, out = _handle_replicate(
                st, out, msg, lmsg & (mt == MT_REPLICATE), slot_i
            )
            st, out = _handle_heartbeat(
                st, out, msg, lmsg & (mt == MT_HEARTBEAT)
            )
            tn = (
                foll
                & (mt == MT_TIMEOUT_NOW)
                & (st.role == ROLE_FOLLOWER)
                & _self_is_voter(st)
            )
            st, out = _campaign(
                st, out, tn, jnp.zeros((st.G,), bool), jnp.ones((st.G,), bool),
                E,
            )
            return st, out

        st, out = _gate(
            _has(MT_REPLICATE, MT_HEARTBEAT, MT_TIMEOUT_NOW), _foll, st, out
        )
        return st, out

    return _gate(jnp.any(rest), _non_tick, st, out)


def _slot_view(inbox: Inbox, i):
    """Slot i of every row ([G] / [E, G] views) from an INTERNAL-layout
    inbox ([M, G] / [M, E, G]); i may be traced."""

    def ix(a):
        return lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False)

    return {
        "mtype": ix(inbox.mtype),
        "from_id": ix(inbox.from_id),
        "term": ix(inbox.term),
        "log_term": ix(inbox.log_term),
        "log_index": ix(inbox.log_index),
        "commit": ix(inbox.commit),
        "reject": ix(inbox.reject),
        "hint": ix(inbox.hint),
        "hint_high": ix(inbox.hint_high),
        "n_entries": ix(inbox.n_entries),
        "ent_term": ix(inbox.ent_term),
        "ent_cc": ix(inbox.ent_cc),
    }


def _step_impl(
    state: DeviceState, cin: Inbox, out_capacity: int
) -> Tuple[DeviceState, DeviceOut]:
    """The step body over INTERNAL-layout operands: state peer/ring
    arrays [P, G]/[W, G], inbox [M, G]/[M, E, G].  Returns internal
    layout.  ``step`` wraps this with the boundary transposes;
    ``step_internal`` exposes it directly so device-resident loops
    never pay the padded-layout boundary traffic (~12 ms/launch at
    300k rows, round 5, remote link)."""
    G = state.G
    P = _P(state)
    M = cin.mtype.shape[0]
    E = cin.ent_term.shape[1]
    out = _make_out_internal(G, P, M, E, out_capacity)
    # inherit the state's varying-ness (shard_map vma) so the loop carry
    # types match when the step runs sharded over the groups axis; every
    # out array is G-trailing, so a bare [G] zero broadcasts onto all
    zero = state.term * 0  # [G]
    out = jax.tree.map(lambda a: a + zero, out)

    # slot compaction: a slot pass costs the same whether the slot is
    # empty or not, and the assembled colocated inbox is mostly-empty
    # routed lanes (P*budget + M slots, typically 2-6 occupied).
    # Stable-sort each row's occupied slots to the front (empty slots
    # are exact no-ops in _process_slot, and the stable key preserves
    # the replay order of the occupied ones), then run only as many
    # passes as the BUSIEST row needs.  The while_loop's data-dependent
    # trip count replaces M static iterations.
    occ = cin.mtype != 0  # [M, G]
    order = jnp.argsort(jnp.where(occ, 0, 1), axis=0, stable=True)

    def compact(a):
        # one-hot permutation, not take_along_axis (per-lane gathers
        # serialize on TPU — see _col)
        return _permute0(a, order)

    cin = Inbox(*(compact(getattr(cin, f)) for f in Inbox._fields))
    # IMPORTANT: out's slot arrays (slot_base/slot_term/ent_drop and
    # src_slot lanes) are reported in COMPACTED coordinates; map them
    # back to the original slot indices afterwards so the host staging
    # keys still match.
    n_occ = jnp.max(jnp.sum(occ.astype(jnp.int32), axis=0))

    def cond(carry):
        i, _st, _o = carry
        return i < n_occ

    def body(carry):
        i, st, o = carry
        st, o = _process_slot(st, o, _slot_view(cin, i), i, E)
        return (i + 1, st, o)

    _, state, out = lax.while_loop(cond, body, (jnp.int32(0), state, out))
    # un-compact the per-slot output arrays back to caller coordinates:
    # compacted slot j of row g corresponds to original slot order[j, g]
    inv = jnp.argsort(order, axis=0, stable=True)

    def uncompact(a):
        return _permute0(a, inv)

    # src_slot values inside the outbox buffer index COMPACTED slots;
    # translate through order so the host sees original coordinates
    src = out.buf[:, F_SRC_SLOT, :]  # [O, G]
    src_ok = src >= 0
    srcc = jnp.clip(src, 0, M - 1)
    # src_orig[o, g] = order[srcc[o, g], g] — one-hot select over M
    sel = srcc[None, :, :] == jnp.arange(M, dtype=srcc.dtype)[:, None, None]
    src_orig = jnp.sum(jnp.where(sel, order[:, None, :], 0), axis=0)
    buf = out.buf.at[:, F_SRC_SLOT, :].set(jnp.where(src_ok, src_orig, src))
    out = out._replace(
        buf=buf,
        slot_base=uncompact(out.slot_base),
        slot_term=uncompact(out.slot_term),
        ent_drop=uncompact(out.ent_drop),
    )
    return state, out


@functools.partial(jax.jit, static_argnames=("out_capacity",))
def step(
    state: DeviceState, inbox: Inbox, out_capacity: int = 32
) -> Tuple[DeviceState, DeviceOut]:
    """Advance every row through its inbox.  Pure and jit-compiled; the
    host wrapper (ops/engine.py) owns staging, payload logs and the
    escalation replay.

    External layout in and out (``[G, ...]`` everywhere); internally the
    whole loop runs G-last so int32 operands pack the 128-lane axis
    instead of padding it 16-42x (see the module docstring).

    Slots run under ``lax.while_loop`` so the compiled program contains
    ONE slot body regardless of M — compile time stays flat and XLA
    still fuses the whole body into a few kernels per slot iteration.
    """
    state = _state_to_internal(state)
    cin = _inbox_to_internal(inbox)
    state, out = _step_impl(state, cin, out_capacity)
    return _state_from_internal(state), _out_from_internal(out)


@functools.partial(jax.jit, static_argnames=("out_capacity",))
def step_internal(
    state: DeviceState, inbox: Inbox, out_capacity: int = 32
) -> Tuple[DeviceState, DeviceOut]:
    """``step`` without the boundary transposes: all operands and
    results in the INTERNAL (G-last) layout — state peer/ring arrays
    [P, G]/[W, G], inbox [M, G]/[M, E, G], out.buf [O, N_FIELDS, G].

    The padded-layout boundary traffic of ``step`` costs ~12 ms/launch
    at 300k rows (measured r5, real barrier) — more than the slot pass
    itself.  Device-resident loops that keep state in the internal
    layout across launches skip it entirely; hosts can
    build internal-layout operands directly in numpy (a host-side
    transpose is a cheap packed copy) via ``state_to_internal``.
    """
    return _step_impl(state, inbox, out_capacity)


def state_to_internal(st: DeviceState) -> DeviceState:
    """Public [G, ...] -> internal (G-last) state layout.  Works on jnp
    or numpy fields (transpose is a view host-side).  The transpose is
    its own inverse; internal-layout Inbox/DeviceOut construction stays
    module-private until a second consumer exists."""
    return _state_to_internal(st)


def make_step_sharded(mesh, *, out_capacity: int):  # mesh-hot
    """Build the shard_map'd step over a 1-D groups mesh (ROADMAP 3).

    Returns a jitted ``(state, inbox) -> (state', out)`` whose program
    runs PER DEVICE on that device's G-slice: the step body is
    row-local (every reduction is over the P/W/M/O axes, never G), so
    the compiled per-shard program contains ZERO collectives and is
    bit-identical to the single-device ``step`` on the concatenation of
    the slices (pinned by tests/test_multichip.py).  The only
    shard-local quantity is the slot-compaction trip count ``n_occ``
    (a per-shard max): a shard with emptier inboxes runs fewer slot
    passes, which is exactly the empty-slot no-op contract.
    """
    import jax as _jax

    from jax import shard_map as _shard_map
    from jax.sharding import PartitionSpec as _PS

    if len(mesh.axis_names) != 1:
        raise ValueError("groups mesh must be one-dimensional")
    axis = mesh.axis_names[0]

    def _local(st, ib):
        return step(st, ib, out_capacity=out_capacity)

    # G leads every leaf: a single prefix spec covers each pytree
    return _jax.jit(
        _shard_map(
            _local, mesh=mesh, in_specs=(_PS(axis), _PS(axis)),
            out_specs=(_PS(axis), _PS(axis)),
            # every spec here is sharded, so the varying-axes check
            # has nothing to verify; skip its trace-time cost
            check_vma=False,
        )
    )
