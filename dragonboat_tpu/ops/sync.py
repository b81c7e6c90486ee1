"""Oracle <-> device-row conversion and message staging.

Three jobs:

  1. ``state_from_rafts`` — pack scalar ``Raft`` oracles into a
     ``DeviceState`` (parity tests, engine bootstrap, escalation return).
  2. ``raft_to_row`` / ``assert_row_matches`` — read a row back out for
     differential comparison or host-side replay.
  3. ``encode_inbox`` / ``decode_out`` — Message lists <-> tensor batches.

The slot layout contract: peer slots hold the union of voters,
non-votings and witnesses sorted by replica id; empty slots are 0.  The
same ordering governs the oracle's sorted broadcast loops, so device and
host iterate peers identically.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..pb import Message, MessageType
from ..raft.log import LogCompactedError
from ..raft.raft import Raft, RaftRole
from .types import (
    DeviceOut,
    DeviceState,
    F_COMMIT,
    F_HINT,
    F_HINT_HIGH,
    F_LOG_INDEX,
    F_LOG_TERM,
    F_MTYPE,
    F_N_ENTRIES,
    F_REJECT,
    F_SRC_SLOT,
    F_TERM,
    F_TO,
    KIND_NON_VOTING,
    KIND_VOTER,
    KIND_WITNESS,
    Inbox,
    PackedState,
    make_state_np,
    pack_state,
)

import jax.numpy as jnp


def peer_layout(raft: Raft) -> List[Tuple[int, int]]:
    """[(replica_id, kind)] sorted by id — the canonical slot order."""
    out = []
    for pid in raft.remotes:
        out.append((pid, KIND_VOTER))
    for pid in raft.non_votings:
        out.append((pid, KIND_NON_VOTING))
    for pid in raft.witnesses:
        out.append((pid, KIND_WITNESS))
    return sorted(out)


def state_from_rafts(
    rafts: Sequence[Raft], P: int, W: int,
    bases: Optional[Sequence[int]] = None,
    pad_to: int = 0,
) -> DeviceState:
    """Pack oracles into a DeviceState, copying the full volatile state
    (not just a fresh boot) so escalated rows can return to the device.

    ``bases``: optional per-row int64 index base subtracted from every
    log-index field (committed/last/first/match/next/snap) so rows whose
    absolute indexes exceed int32 stay device-steppable — the engine's
    64-bit story (the host WAL is 64-bit throughout; the device works in
    a rebased window).  Each base MUST be a multiple of W so the ring
    slot of an index is invariant under the shift ((abs-base) % W ==
    abs % W), and must not exceed any live index quantity of its row.

    ``pad_to``: pad the row axis to this length by repeating the last
    row, IN NUMPY — callers used to pad with eager jnp slice/repeat/
    concat per field, and on a remote TPU link every first-per-shape
    eager op is a fresh tiny compile (~31 fields x 3 ops x ~0.4 s ate
    46% of the r4 10k-shard election as "upload" time).
    """
    cols = _state_cols(rafts, P, W, bases, pad_to)
    return DeviceState(**{k: jnp.asarray(v) for k, v in cols.items()})


def packed_state_from_rafts(
    rafts: Sequence[Raft], P: int, W: int,
    bases: Optional[Sequence[int]] = None,
    pad_to: int = 0,
) -> PackedState:
    """``state_from_rafts`` as host (numpy) blocks: the engine uploads
    its rows in the packed form (types.PackedState) in one transfer."""
    return pack_state(
        DeviceState(**_state_cols(rafts, P, W, bases, pad_to)), np
    )


def _state_cols(rafts, P, W, bases, pad_to) -> Dict[str, np.ndarray]:
    G = len(rafts)
    # pure-NUMPY staging end to end: make_state_np never touches the
    # device, so packing costs no device->host readbacks (31 per batch
    # before — the dominant upload cost on a remote TPU link, r4 SCALE)
    base_cols = make_state_np(
        G,
        P,
        W,
        shard_ids=[r.shard_id for r in rafts],
        replica_ids=[r.replica_id for r in rafts],
        peer_ids=_peer_ids(rafts, P),
        peer_kinds=_peer_kinds(rafts, P),
    )
    # int64 staging: absolute indexes may exceed int32 before the shift
    cols: Dict[str, np.ndarray] = {
        k: v.astype(np.int64) for k, v in base_cols.items()
    }
    for g, r in enumerate(rafts):
        _fill_row(cols, g, r, P, W)
        if bases is not None and bases[g]:
            b = int(bases[g])
            assert b % W == 0, f"row {g}: base {b} not a multiple of W"
            for k in ("committed", "last_index", "first_index"):
                cols[k][g] -= b
            for k in ("match", "next_idx", "snap_index"):
                row = cols[k][g]
                row[row > 0] -= b
                # stale lanes below the base (a non-leader's boot-time
                # next=1 etc.) clamp to the 0 sentinel: they are dead
                # state that the next election resets anyway, and
                # negative lanes would wrap int32
                row[row < 0] = 0
    out: Dict[str, np.ndarray] = {}
    for k, v in cols.items():
        if (v > 2**31 - 1).any() or (v < -(2**31)).any():
            raise OverflowError(
                f"state field {k} exceeds int32 after rebase"
            )
        v = v.astype(np.int32)
        if pad_to > v.shape[0]:
            v = np.concatenate(
                [v, np.repeat(v[-1:], pad_to - v.shape[0], axis=0)]
            )
        out[k] = v
    return out


def _peer_ids(rafts, P):
    G = len(rafts)
    out = np.zeros((G, P), np.int32)
    for g, r in enumerate(rafts):
        lay = peer_layout(r)
        if len(lay) > P:
            raise ValueError(f"row {g}: {len(lay)} peers > P={P}")
        for s, (pid, _) in enumerate(lay):
            out[g, s] = pid
    return out


def _peer_kinds(rafts, P):
    G = len(rafts)
    out = np.zeros((G, P), np.int32)
    for g, r in enumerate(rafts):
        for s, (_, kind) in enumerate(peer_layout(r)):
            out[g, s] = kind
    return out


def _fill_row(cols, g, r: Raft, P, W):
    cols["election_timeout"][g] = r.election_timeout
    cols["heartbeat_timeout"][g] = r.heartbeat_timeout
    cols["check_quorum"][g] = int(r.check_quorum)
    cols["pre_vote"][g] = int(r.pre_vote)
    cols["term"][g] = r.term
    cols["vote"][g] = r.vote
    cols["leader_id"][g] = r.leader_id
    cols["role"][g] = int(r.role)
    cols["committed"][g] = r.log.committed
    last = r.log.last_index()
    cols["last_index"][g] = last
    while True:
        # a snapshot worker may compact the log under this read
        # (node.py "snapshotting"): first only ever grows, so take it
        # again until the window was read whole
        first = r.log.first_index()
        try:
            _fill_log_window(cols, g, r, first, last, W)
            break
        except LogCompactedError:
            if r.log.first_index() == first:
                raise
    cols["election_tick"][g] = r.election_tick
    cols["heartbeat_tick"][g] = r.heartbeat_tick
    cols["rand_timeout"][g] = r.randomized_election_timeout
    cols["timeout_seq"][g] = r._timeout_seq
    cols["pending_cc"][g] = int(r.pending_config_change)
    cols["transfer_target"][g] = r.leader_transfer_target
    for s, (pid, _) in enumerate(peer_layout(r)):
        rm = r.get_remote(pid)
        cols["match"][g, s] = rm.match
        cols["next_idx"][g, s] = rm.next
        cols["rstate"][g, s] = int(rm.state)
        cols["snap_index"][g, s] = rm.snapshot_index
        cols["active"][g, s] = int(rm.active)
        if pid in r.votes:
            cols["granted"][g, s] = 1 if r.votes[pid] else 2


def _fill_log_window(cols, g, r: Raft, first: int, last: int, W: int) -> None:
    cols["first_index"][g] = first
    try:
        cols["base_term"][g] = r.log.term(first - 1) if first > 1 else 0
    except LogCompactedError:
        raise  # the boundary moved under the read: _fill_row takes it again
    except Exception:
        cols["base_term"][g] = 0
    for idx in range(max(first, last - W + 1), last + 1):
        t = r.log.term(idx)
        cols["ring_term"][g, idx % W] = t
        ents = r.log._get_entries(idx, idx + 1, 2**62)
        cols["ring_cc"][g, idx % W] = int(bool(ents and ents[0].is_config_change()))


ROW_SCALARS = (
    "term",
    "vote",
    "leader_id",
    "role",
    "committed",
    "last_index",
    "election_tick",
    "heartbeat_tick",
    "rand_timeout",
    "timeout_seq",
    "pending_cc",
    "transfer_target",
)
ROW_PEER = ("match", "next_idx", "rstate", "snap_index", "active", "granted")


def raft_to_row(r: Raft, P: int, W: int) -> dict:
    """The oracle's state in row form (for comparisons)."""
    cols = {
        k: np.zeros((1,), np.int32)
        for k in ROW_SCALARS
        + ("election_timeout", "heartbeat_timeout", "check_quorum", "pre_vote",
           "base_term", "first_index")
    }
    for k in ROW_PEER:
        cols[k] = np.zeros((1, P), np.int32)
    cols["ring_term"] = np.zeros((1, W), np.int32)
    cols["ring_cc"] = np.zeros((1, W), np.int32)
    _fill_row(cols, 0, r, P, W)
    return {k: v[0] for k, v in cols.items()}


def row_diff(state: DeviceState, g: int, r: Raft) -> List[str]:
    """Human-readable field mismatches between device row g and oracle."""
    want = raft_to_row(r, state.P, state.W)
    errs = []
    for k in ROW_SCALARS:
        got = int(np.asarray(getattr(state, k))[g])
        if got != int(want[k]):
            errs.append(f"{k}: device={got} oracle={int(want[k])}")
    for k in ROW_PEER:
        got = np.asarray(getattr(state, k))[g]
        if k == "active":
            # bit 0 is the oracle's Remote.active; bit 1 (answered
            # since the last tick feed) has no scalar twin
            got = got & 1
        if not np.array_equal(got, want[k]):
            errs.append(f"{k}: device={got.tolist()} oracle={want[k].tolist()}")
    # ring: compare only the in-window slice
    last = r.log.last_index()
    first = r.log.first_index()
    win_lo = max(first, last - state.W + 1)
    ring_d = np.asarray(state.ring_term)[g]
    ring_cc_d = np.asarray(state.ring_cc)[g]
    for idx in range(win_lo, last + 1):
        if ring_d[idx % state.W] != r.log.term(idx):
            errs.append(
                f"ring_term[{idx}]: device={ring_d[idx % state.W]} "
                f"oracle={r.log.term(idx)}"
            )
        if ring_cc_d[idx % state.W] != want["ring_cc"][idx % state.W]:
            errs.append(f"ring_cc[{idx}] mismatch")
    return errs


# ---------------------------------------------------------------------------
# inbox / outbox staging
# ---------------------------------------------------------------------------
INBOX_FIELDS = (
    "mtype",
    "from_id",
    "term",
    "log_term",
    "log_index",
    "commit",
    "reject",
    "hint",
    "hint_high",
    "n_entries",
)


def inbox_row_ints(M: int, E: int) -> int:
    """Ints one inbox row packs into: the ten ``[M]`` fields, then
    ``ent_term`` and ``ent_cc`` (``[M, E]`` each), in ``Inbox`` order."""
    return M * (len(INBOX_FIELDS) + 2 * E)


_I32_MIN, _I32_MAX = -2**31, 2**31 - 1


def _fits_i32(vals):
    """``vals`` (a non-empty sequence of ints bound for an int32 slice),
    refused where one is outside int32: numpy casts a sequence stored
    into a slice unsafely, so the value would wrap and the device read
    another message."""
    if min(vals) < _I32_MIN or max(vals) > _I32_MAX:
        raise OverflowError(f"inbox field outside int32: {vals}")
    return vals


def encode_inbox_np(
    batches: Sequence[Sequence[Message]], M: int, E: int,
    out: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, List[int]]:
    """Pack per-row ordered Message lists into a ``[G, inbox_row_ints]``
    int32 block, one row each, row-major: field f's slot i at column
    ``f*M + i``, then ``ent_term[i, j]`` at ``10*M + i*E + j`` and
    ``ent_cc`` ``M*E`` further.  ``out`` (all zero, at least
    ``len(batches)`` rows) is filled in place.

    Returns (block, overflow_rows): rows whose batch exceeds M slots or
    whose REPLICATE carries more than E entries must be host-stepped.
    A field outside int32 raises ``OverflowError``.
    """
    G = len(batches)
    NF = len(INBOX_FIELDS)
    if out is None:
        out = np.zeros((G, inbox_row_ints(M, E)), np.int32)
    ent_term = NF * M
    ent_cc = ent_term + M * E
    overflow: List[int] = []
    for g, msgs in enumerate(batches):
        if len(msgs) > M:
            overflow.append(g)
            continue
        row = out[g]
        for i, m in enumerate(msgs):
            n = len(m.entries)
            if n > E:
                overflow.append(g)
                break
            # INBOX_FIELDS order, one strided store a message
            row[i:ent_term:M] = _fits_i32((
                int(m.type), m.from_, m.term, m.log_term, m.log_index,
                m.commit, int(m.reject), m.hint, m.hint_high, n,
            ))
            if n:
                at = i * E
                row[ent_term + at:ent_term + at + n] = _fits_i32(
                    [e.term for e in m.entries]
                )
                row[ent_cc + at:ent_cc + at + n] = [
                    int(e.is_config_change()) for e in m.entries
                ]
    return out, overflow


def unpack_inbox(block, M: int, E: int) -> Inbox:
    """The ``Inbox`` view of an ``encode_inbox_np`` block (numpy or
    traced): ``[G, M]`` fields and ``[G, M, E]`` entry lanes."""
    G = block.shape[0]
    NF = len(INBOX_FIELDS)
    cols = [block[:, f * M:(f + 1) * M] for f in range(NF)]
    ents = block[:, NF * M:].reshape(G, 2, M, E)
    return Inbox(*cols, ent_term=ents[:, 0], ent_cc=ents[:, 1])


def encode_inbox(
    batches: Sequence[Sequence[Message]], M: int, E: int
) -> Tuple[Inbox, List[int]]:
    """``encode_inbox_np`` as a device ``Inbox`` (one array a field)."""
    block, overflow = encode_inbox_np(batches, M, E)
    return Inbox(*map(jnp.asarray, unpack_inbox(block, M, E))), overflow


def decode_out_row(
    out_np: dict, g: int, shard_id: int, replica_id: int
) -> List[Tuple[Message, int, int]]:
    """Outbox row -> [(message, n_entries, src_slot)].

    Entry payloads are attached by the host from its payload log
    (REPLICATE: indexes [log_index+1, log_index+n]; forwarded PROPOSE:
    the staged entries of inbox slot ``src_slot``)."""
    n = int(out_np["count"][g])
    buf = out_np["buf"][g]
    msgs = []
    for k in range(n):
        rec = buf[k]
        msgs.append(
            (
                Message(
                    type=MessageType(int(rec[F_MTYPE])),
                    to=int(rec[F_TO]),
                    from_=replica_id,
                    shard_id=shard_id,
                    term=int(rec[F_TERM]),
                    log_term=int(rec[F_LOG_TERM]),
                    log_index=int(rec[F_LOG_INDEX]),
                    commit=int(rec[F_COMMIT]),
                    reject=bool(rec[F_REJECT]),
                    hint=int(rec[F_HINT]),
                    hint_high=int(rec[F_HINT_HIGH]),
                ),
                int(rec[F_N_ENTRIES]),
                int(rec[F_SRC_SLOT]),
            )
        )
    return msgs


def out_to_numpy(out: DeviceOut) -> dict:
    return {k: np.asarray(getattr(out, k)) for k in out._fields}
