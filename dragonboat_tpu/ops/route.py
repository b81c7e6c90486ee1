"""Device-side message routing: outbox -> co-located peer inboxes.

The reference's step workers hand every outbound message to the
transport, even when the destination replica lives in the same process
(reference: engine.go stepWorkerMain -> transport.Send [U]; the in-proc
loopback only short-circuits the socket).  On TPU that host detour is
the scaling bottleneck: at 100k groups x 3 replicas every row's traffic
would round-trip device->host->device each step.

``route`` keeps intra-device traffic ON the device: messages in a
``DeviceOut`` buffer whose destination replica is resident on the same
chip are scattered straight into the next step's ``Inbox``.  Combined
with ``ops/kernel.step`` this closes the loop — elections, replication
and commit advance run entirely device-side.

Routing is **best-effort**: anything the router cannot deliver (peer
off-device, per-sender slot budget exhausted, REPLICATE entries no
longer reconstructible from the sender's ring) is DROPPED and counted.
Raft tolerates arbitrary message loss — drops cost retries, never
safety — so the fast path needs no overflow side-channel.

Slot assignment is direct-mapped, not sorted: the inbox is laid out as

    [0, base)                      host/injected slots (ticks, proposals)
    [base + r*budget, +budget)     messages from the sender holding slot
                                   r in the DESTINATION row's peer table

so a message's target slot is a pure per-message computation (one
cumulative count per sender), with no cross-row sort.  Per-sender
in-order delivery is preserved.  ``base + P*budget == M`` must hold
exactly: the inbox IS the concatenation of the prefill columns and the
per-sender regions (route() assembles it by reshape, not scatter).

Static tables (host-precomputed, see ``build_route_tables``):
  dest_row[g, p]      device row hosting (shard_id[g], peer_id[g, p]),
                      -1 when that replica is not on this device/shard
  rank_in_dest[g, p]  the slot index row g's replica occupies in THAT
                      row's peer table (the region selector above)
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
    F_TERM,
    F_TO,
    I32,
    Inbox,
    MT_PROPOSE,
    MT_REPLICATE,
    MT_TICK,
    ROLE_LEADER,
)


class RouteStats(NamedTuple):
    """Per-call routing outcome counters (all scalars)."""

    delivered: jnp.ndarray
    dropped_off_device: jnp.ndarray   # destination replica not resident
    dropped_budget: jnp.ndarray       # per-sender region full
    dropped_ring: jnp.ndarray         # REPLICATE entries aged out of ring
    suppressed: jnp.ndarray           # messages of escalated source rows
    host_carried: jnp.ndarray         # deliberately left to the host path
    #                                   (forwarded PROPOSE, dest row dirty)

    def __add__(self, other: "RouteStats") -> "RouteStats":
        return RouteStats(*(a + b for a, b in zip(self, other)))


def build_route_tables(  # raftlint: ignore[host-sync] host-side numpy precompute of static tables
    shard_ids: np.ndarray,
    replica_ids: np.ndarray,
    peer_ids: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side precompute of (dest_row, rank_in_dest) for a row layout.

    Rows are identified by (shard, replica); a peer slot whose replica is
    not hosted in this layout routes to -1 (off-device -> transport).
    """
    G, P = peer_ids.shape
    row_of: Dict[Tuple[int, int], int] = {
        (int(s), int(r)): g
        for g, (s, r) in enumerate(zip(shard_ids, replica_ids))
    }
    # per-row {pid: slot} so rank lookup is O(1), not a nonzero scan
    slot_of = [
        {int(pid): p for p, pid in enumerate(row) if pid}
        for row in peer_ids
    ]
    dest_row = np.full((G, P), -1, np.int32)
    rank_in_dest = np.zeros((G, P), np.int32)
    for g in range(G):
        shard = int(shard_ids[g])
        me = int(replica_ids[g])
        for p in range(P):
            pid = int(peer_ids[g, p])
            if pid == 0:
                continue
            d = row_of.get((shard, pid))
            if d is None:
                continue
            mine = slot_of[d].get(me)
            if mine is None:
                # destination doesn't know us (mid-membership-change):
                # no slot region is ours, and borrowing rank 0 would
                # silently collide with the real rank-0 sender — leave
                # it off-device so the drop is counted (or the host
                # transport carries it)
                continue
            dest_row[g, p] = d
            rank_in_dest[g, p] = mine
    return dest_row, rank_in_dest


def route(
    state: DeviceState,
    out: DeviceOut,
    dest_row: jnp.ndarray,
    rank_in_dest: jnp.ndarray,
    *,
    M: int,
    E: int,
    budget: int,
    base: int,
    base_inbox: Optional[Inbox] = None,
    suppress: Optional[jnp.ndarray] = None,
    dest_alive: Optional[jnp.ndarray] = None,
) -> Tuple[Inbox, RouteStats, jnp.ndarray]:
    """Scatter ``out``'s messages into a fresh (or prefilled) Inbox.

    ``state`` must be the POST-step state of the sending rows: REPLICATE
    payloads are reconstructed from the sender's log-term ring, which
    holds the entries appended in the step that emitted the message.
    ``suppress`` masks source rows whose device effects were discarded
    (escalations): their messages must not be delivered.
    ``dest_alive`` ([G] bool) masks DESTINATION rows that must not be fed
    (engine rows on the host/scalar path): messages to them are left
    undelivered so the host transport can carry them instead.

    Returns ``(inbox, stats, delivered)`` where ``delivered`` is a
    [G, O] bool — True where outbox message o of row g was scattered
    into a peer row (the engine skips host decode for those).  Two
    message classes are never device-delivered even when the peer is
    resident: forwarded PROPOSE (its cmd payload exists only on the
    sending host) and anything addressed to the sender itself (the
    kernel's host-coordination READ_INDEX_RESP).
    """
    G, O, _ = out.buf.shape
    P = state.P
    W = state.W
    B = budget
    if base + P * B != M:
        raise ValueError(
            f"inbox layout mismatch: base={base} + P={P} * budget={B} "
            f"must equal M={M} (the inbox IS the region layout)"
        )

    # NOTE on lowering: NO arbitrary-index scatter anywhere (TPU lowers
    # data-dependent scatters to a serial loop — measured ~20x) and
    # per-ELEMENT gathers are avoided too (~18 ns/element serialized,
    # measured r5 — a dozen [G,P,B] field gathers dominated the round).
    # The only gather left is ONE cross-row gather of packed per-sender
    # rows (row gathers amortize to ~1 ns/element); everything else is
    # one-hot select / reduce over a small axis.  The direct-mapped slot
    # layout makes the inbox exactly
    # ``concat([prefill, region(r=0), ..., region(r=P-1)], axis=1)``.

    buf = out.buf
    mtype = buf[:, :, F_MTYPE]
    to = buf[:, :, F_TO]
    n_ent = buf[:, :, F_N_ENTRIES]
    log_index = buf[:, :, F_LOG_INDEX]
    log_term = buf[:, :, F_LOG_TERM]

    valid = jnp.arange(O)[None, :] < out.count[:, None]
    n_suppressed = jnp.zeros((), I32)
    if suppress is not None:
        n_suppressed = jnp.sum(valid & suppress[:, None], dtype=I32)
        valid = valid & ~suppress[:, None]

    # destination peer slot in the SENDER's table
    hits = (
        (state.peer_id[:, None, :] == to[:, :, None])
        & (to[:, :, None] != 0)
        & (state.peer_id[:, None, :] != 0)
    )  # [G, O, P]
    found = jnp.any(hits, axis=2)
    routable = valid & found

    # per-peer destination facts, [G, P] (static tables — elementwise)
    dest_ge0 = dest_row >= 0
    dest_not_self = dest_row != jnp.arange(G)[:, None]
    if dest_alive is not None:
        # [G, P] per-element gather over the static table: tiny next to
        # the per-message alternative (dest_alive[dest] was [G, O])
        alive_tab = dest_alive[jnp.clip(dest_row, 0, G - 1)] & dest_ge0
    else:
        alive_tab = dest_ge0

    def at_pstar(tab):  # tab [G, P] -> per-message [G, O] via the one-hot
        return jnp.any(hits & tab[:, None, :], axis=2)

    on_device = routable & at_pstar(dest_ge0)

    # deliverability per MESSAGE (sender side; used for selection + stats)
    is_repl = mtype == MT_REPLICATE
    carries = is_repl & (n_ent > 0)
    win_lo = jnp.maximum(state.first_index, state.last_index - (W - 1))
    # a log_term=0 marker on a nonzero prev is the kernel's below-ring
    # HOST-FIXUP request (_send_replicate): the true prev term must be
    # stamped by the sender's host before delivery.  The entries-only
    # window check passes at prev == win_lo - 1 (entries start at
    # prev+1), so without this the one-below-window REPLICATE would be
    # device-delivered with a fake prev term (review finding).
    marker = is_repl & (log_index > 0) & (log_term == 0)
    ring_ok = ~carries | (
        (log_index + 1 >= win_lo[:, None])
        & (log_index + n_ent <= state.last_index[:, None])
        & ~marker
    )

    # host-only classes: forwarded PROPOSE (cmd bytes never reach the
    # device) and self-addressed coordination messages; plus messages
    # whose destination row is currently host-authoritative (dirty)
    not_propose = mtype != MT_PROPOSE
    msg_ok = not_propose & at_pstar(dest_not_self) & at_pstar(alive_tab)

    # per-sender emission index toward each peer slot, counted over
    # DELIVERABLE messages only — host-carried/ring-stale messages must
    # not consume budget ranks they will never occupy (their slot would
    # sit empty while a later deliverable message got pushed past B)
    deliverable = valid & ring_ok & msg_ok  # [G, O]
    oh = (hits & deliverable[:, :, None]).astype(I32)  # [G, O, P]
    k_excl = jnp.cumsum(oh, axis=1) - oh
    k = jnp.sum(jnp.where(hits, k_excl, 0), axis=2)  # k_excl at p_star

    # SENDER-side selection + packing.  m_b (at most one outbox slot per
    # (g, p, b)) doubles as the one-hot selector for every field — no
    # o_sel index materialization, no per-element field gathers.
    sendable = hits & deliverable[:, :, None]  # [G, O, P]
    sel_b = []
    for b in range(B):
        sel_b.append(sendable & (k_excl == b))
    send_sel = jnp.stack(sel_b, axis=3)  # [G, O, P, B]
    pick_found = jnp.any(send_sel, axis=1)  # [G, P, B]

    def pick(col):  # [G, P, B]: buf[g, o_sel[g,p,b], col] via one-hot
        return jnp.sum(
            jnp.where(send_sel, buf[:, :, col][:, :, None, None], 0),
            axis=1,
        )

    wire_cols = (
        F_MTYPE, F_TERM, F_LOG_TERM, F_LOG_INDEX, F_COMMIT,
        F_REJECT, F_HINT, F_HINT_HIGH, F_N_ENTRIES,
    )
    picked = {c: pick(c) for c in wire_cols}

    # REPLICATE payload, sender-side: ring terms/cc at [li+1, li+n] via
    # one-hot over the W ring positions (per-element ring gathers were
    # the single most expensive op of the old route)
    li_pb = picked[F_LOG_INDEX]
    n_pb = picked[F_N_ENTRIES]
    repl_pb = pick_found & (picked[F_MTYPE] == MT_REPLICATE)
    wm = W - 1
    went = []
    for e in range(E):
        pos = (jnp.clip(li_pb + 1 + e, 0, None) & wm)  # [G, P, B]
        selw = (
            pos[:, :, :, None] == jnp.arange(W)[None, None, None, :]
        )  # [G, P, B, W]
        has_e = repl_pb & (e < n_pb)
        et = jnp.sum(
            jnp.where(selw, state.ring_term[:, None, None, :], 0), axis=3
        )
        ec = jnp.sum(
            jnp.where(selw, state.ring_cc[:, None, None, :], 0), axis=3
        )
        went.append((
            jnp.where(has_e, et, 0), jnp.where(has_e, ec, 0),
        ))
    ent_term_s = jnp.stack([t for t, _ in went], axis=3)  # [G, P, B, E]
    ent_cc_s = jnp.stack([c for _, c in went], axis=3)

    # pack everything a receiver needs into one row per (sender, slot):
    # 9 wire fields + found + from_id + E terms + E cc bits
    from_pb = jnp.broadcast_to(
        state.replica_id[:, None, None], (G, P, B)
    )
    pack = jnp.stack(
        [picked[c] for c in wire_cols]
        + [pick_found.astype(I32), from_pb],
        axis=3,
    )  # [G, P, B, 11]
    # packed-row layout (single source of truth for the unpack below)
    IDX_FOUND = len(wire_cols)      # found flag
    IDX_FROM = len(wire_cols) + 1   # sender replica id
    KF = len(wire_cols) + 2         # ent_term starts here
    pack = jnp.concatenate([pack, ent_term_s, ent_cc_s], axis=3)
    KT = KF + 2 * E
    packr = pack.reshape(G * P, B * KT)

    # dest-side assembly: for dest d, region r is fed by the replica in
    # d's peer slot r; in THAT sender's table, d occupies slot
    # rank_in_dest[d, r] (the mapping is symmetric by construction).
    # ONE cross-row row-gather moves the packed rows.
    src = dest_row                                   # [G, P] (as dest view)
    src_ok = src >= 0
    src_c = jnp.clip(src, 0, G - 1)
    flat = (src_c * P + rank_in_dest).reshape(-1)    # [G*P]
    region = packr[flat].reshape(G, P, B, KT)
    # region r of row d must not be fed by d itself (its own slot)
    not_self_d = src_c != jnp.arange(G)[:, None]
    sel_found = (
        (region[:, :, :, IDX_FOUND] != 0)
        & src_ok[:, :, None]
        & not_self_d[:, :, None]
    )  # [G, P, B]

    def field(i):  # unpack + mask + flatten one received field
        return jnp.where(sel_found, region[:, :, :, i], 0).reshape(G, P * B)

    if base_inbox is None:
        base_inbox = make_prefill(state, M, E, tick=False)
    pre = {k_: getattr(base_inbox, k_)[:, :base] for k_ in (
        "mtype", "from_id", "term", "log_term", "log_index", "commit",
        "reject", "hint", "hint_high", "n_entries",
    )}

    col_at = {c: i for i, c in enumerate(wire_cols)}

    def asm(name, col):
        return jnp.concatenate([pre[name], field(col_at[col])], axis=1)

    ent_term = jnp.where(
        sel_found[:, :, :, None], region[:, :, :, KF:KF + E], 0
    ).reshape(G, P * B, E)
    ent_cc = jnp.where(
        sel_found[:, :, :, None], region[:, :, :, KF + E:KT], 0
    ).reshape(G, P * B, E)

    inbox = Inbox(
        mtype=asm("mtype", F_MTYPE),
        from_id=jnp.concatenate(
            [pre["from_id"], field(IDX_FROM)], axis=1
        ),
        term=asm("term", F_TERM),
        log_term=asm("log_term", F_LOG_TERM),
        log_index=asm("log_index", F_LOG_INDEX),
        commit=asm("commit", F_COMMIT),
        reject=asm("reject", F_REJECT),
        hint=asm("hint", F_HINT),
        hint_high=asm("hint_high", F_HINT_HIGH),
        n_entries=asm("n_entries", F_N_ENTRIES),
        ent_term=jnp.concatenate(
            [base_inbox.ent_term[:, :base], ent_term], axis=1
        ),
        ent_cc=jnp.concatenate(
            [base_inbox.ent_cc[:, :base], ent_cc], axis=1
        ),
    )
    in_budget = k < B
    delivered = valid & found & ring_ok & msg_ok & in_budget  # [G, O]
    stats = RouteStats(
        delivered=jnp.sum(sel_found, dtype=I32),
        dropped_off_device=jnp.sum(
            routable & ~at_pstar(dest_ge0), dtype=I32
        ),
        dropped_budget=jnp.sum(
            on_device & msg_ok & ring_ok & ~in_budget, dtype=I32
        ),
        dropped_ring=jnp.sum(on_device & msg_ok & ~ring_ok, dtype=I32),
        suppressed=n_suppressed,
        host_carried=jnp.sum(on_device & ~msg_ok, dtype=I32),
    )
    return inbox, stats, delivered


def make_prefill(
    state: DeviceState,
    M: int,
    E: int,
    *,
    tick: bool = True,
    propose_leaders: bool = False,
    propose_n: int = 1,
) -> Inbox:
    """Injected inbox prefix: slot 0 = LOCAL_TICK for every row, slot 1 =
    a ``propose_n``-entry PROPOSE on rows currently leading (a device-
    side load generator; empty slots stay NO_OP and cost nothing)."""
    G = state.G

    def zm():
        # distinct buffers per field: aliased zeros break donate_argnums
        # (XLA rejects donating the same buffer twice)
        return jnp.zeros((G, M), I32)

    mtype = zm()
    n_entries = zm()
    if tick:
        mtype = mtype.at[:, 0].set(MT_TICK)
    if propose_leaders:
        lead = state.role == ROLE_LEADER
        mtype = mtype.at[:, 1].set(jnp.where(lead, MT_PROPOSE, 0))
        n_entries = n_entries.at[:, 1].set(jnp.where(lead, propose_n, 0))
    return Inbox(
        mtype=mtype, from_id=zm(), term=zm(), log_term=zm(),
        log_index=zm(), commit=zm(), reject=zm(), hint=zm(),
        hint_high=zm(), n_entries=n_entries,
        ent_term=jnp.zeros((G, M, E), I32),
        ent_cc=jnp.zeros((G, M, E), I32),
    )


def merge_and_route(
    old_state: DeviceState,
    new_state: DeviceState,
    out,
    dest_row: jnp.ndarray,
    rank_in_dest: jnp.ndarray,
    *,
    M: int,
    E: int,
    budget: int,
    base: int,
    propose_leaders: bool = False,
    propose_n: int = 1,
) -> Tuple[DeviceState, Inbox, RouteStats, jnp.ndarray]:
    """The post-step tail of a consensus round: undo escalated rows
    (their device effects are discarded — the host-replay contract minus
    the replay; dropping the inputs is raft-safe message loss), then
    route the outboxes into the next round's inbox on top of a fresh
    tick/proposal prefill.  Shared by ``routed_round`` and callers that
    jit step/route as SEPARATE programs for compile time.

    Returns (state', inbox', stats, escalated_row_count).
    """
    esc = out.escalate != 0
    n_esc = jnp.sum(esc, dtype=I32)
    keep = ~esc

    def sel(a, b):
        m = keep.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, b, a)

    state = jax.tree.map(sel, old_state, new_state)
    prefill = make_prefill(
        state, M, E,
        propose_leaders=propose_leaders, propose_n=propose_n,
    )
    inbox, stats, _delivered = route(
        state, out, dest_row, rank_in_dest,
        M=M, E=E, budget=budget, base=base,
        base_inbox=prefill, suppress=esc,
    )
    return state, inbox, stats, n_esc


def routed_round(
    state: DeviceState,
    inbox: Inbox,
    dest_row: jnp.ndarray,
    rank_in_dest: jnp.ndarray,
    *,
    out_capacity: int,
    budget: int,
    base: int,
    propose_leaders: bool = False,
    propose_n: int = 1,
) -> Tuple[DeviceState, Inbox, RouteStats, jnp.ndarray]:
    """One full consensus round: step every row through ``inbox``, then
    ``merge_and_route`` the outboxes into the next round's inbox."""
    from . import kernel as K

    M, E = inbox.M, inbox.E
    new_state, out = K.step(state, inbox, out_capacity=out_capacity)
    return merge_and_route(
        state, new_state, out, dest_row, rank_in_dest,
        M=M, E=E, budget=budget, base=base,
        propose_leaders=propose_leaders, propose_n=propose_n,
    )


def fused_rounds(
    state: DeviceState,
    inbox: Inbox,
    dest_row: jnp.ndarray,
    rank_in_dest: jnp.ndarray,
    *,
    rounds: int,
    out_capacity: int,
    budget: int,
    base: int,
    propose_leaders: bool = False,
    propose_n: int = 1,
) -> Tuple[DeviceState, Inbox, jnp.ndarray, jnp.ndarray]:
    """``rounds`` consecutive consensus rounds chained INSIDE one
    program — the fused commit wave (ISSUE 15 / ROADMAP item 2).

    Each round is exactly :func:`routed_round`: step, discard escalated
    rows, route the outboxes into the next round's inbox.  Chaining
    them device-side means a quiet-path propose -> replicate/ack ->
    commit/deliver sequence (``rounds=3``, the default wave) completes
    in ONE launch with no host round trip between rounds: a 3-round
    commit pays one readback latency instead of three.

    UNROLLED, not ``lax.scan``: ``rounds`` is static and small (2-4),
    per-round stats fall out of the unrolled loop for free, and the
    compile cost is ``rounds`` copies of one round's program — NOT the
    pathological step+route mega-fusion the r5 compile-time finding
    rules out (step and route stay separate jit units at scale
    geometry for exactly that reason; a K-chain of the SAME round
    program reuses its fusion decisions and stays linear).

    Bit-exactness contract: ``fused_rounds(..., rounds=K)`` must equal
    K sequential ``routed_round`` calls, state and inbox, bit for bit
    — the serial-K parity oracle (tests/test_hostplane.py
    ``TestFusedRoundOracle``).

    Returns ``(state', inbox', stats [rounds, 6], n_esc [rounds])`` —
    per-round RouteStats rows and escalation counts (an escalated
    row's effects are discarded in ITS round and the row re-steps in
    later rounds, the same restore-and-continue contract the launch
    pipeline applies across generations)."""
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    stats_l = []
    esc_l = []
    for _ in range(rounds):
        state, inbox, stats, n_esc = routed_round(
            state, inbox, dest_row, rank_in_dest,
            out_capacity=out_capacity, budget=budget, base=base,
            propose_leaders=propose_leaders, propose_n=propose_n,
        )
        stats_l.append(jnp.stack(list(stats)))
        esc_l.append(n_esc)
    return state, inbox, jnp.stack(stats_l), jnp.stack(esc_l)


# ---------------------------------------------------------------------------
# multi-chip device plane: sharded tables + the collective exchange lane
# (ROADMAP item 3 / docs/MULTICHIP.md)
# ---------------------------------------------------------------------------
class MeshTables(NamedTuple):
    """Static route tables for a G-sharded mesh (row-block placement:
    device ``d`` owns global rows [d*Gl, (d+1)*Gl) — ops/placement.py).

    All three are [G, P] (sharded over G like the state), describing the
    peer in each slot of each row:

      dest_dev[g, p]    device hosting that replica (-1: not placed)
      dest_local[g, p]  its LOCAL row index on that device
      rank_in_dest[g, p] the slot index row g's replica occupies in THAT
                        row's peer table (identical to the single-device
                        table — region selection is device-agnostic)
    """

    dest_local: np.ndarray
    dest_dev: np.ndarray
    rank_in_dest: np.ndarray


class CrossStats(NamedTuple):
    """Per-call collective-lane counters (all scalars, per shard)."""

    sent: jnp.ndarray            # messages packed onto the lane
    delivered: jnp.ndarray       # received messages scattered into slots
    dropped_budget: jnp.ndarray  # per-sender region rank >= budget
    dropped_xlane: jnp.ndarray   # per-edge lane slots exhausted (>= XB)
    dropped_ring: jnp.ndarray    # REPLICATE no longer ring-resident


def build_route_tables_mesh(  # raftlint: ignore[host-sync] host-side numpy precompute of static tables
    shard_ids: np.ndarray,
    replica_ids: np.ndarray,
    peer_ids: np.ndarray,
    n_devices: int,
) -> MeshTables:
    """Device-boundary classification of the route tables: the global
    ``build_route_tables`` output split by the row-block placement into
    (device, local-row) coordinates.  A peer on the SAME device routes
    through the ordinary intra-device ``route``; a peer on another
    device rides the collective exchange lane (``cross_exchange``)."""
    G = peer_ids.shape[0]
    if n_devices <= 0 or G % n_devices:
        raise ValueError(f"G={G} must divide over {n_devices} devices")
    gl = G // n_devices
    dest, rank = build_route_tables(shard_ids, replica_ids, peer_ids)
    placed = dest >= 0
    dest_dev = np.where(placed, dest // gl, -1).astype(np.int32)
    dest_local = np.where(placed, dest % gl, -1).astype(np.int32)
    return MeshTables(dest_local, dest_dev, rank)


def xbudget_for(  # raftlint: ignore[host-sync] host-side numpy sizing of a static lane budget
    tables: MeshTables, budget: int, n_devices: int
) -> int:
    """Worst-case per-edge lane volume for ``tables``: for each
    (src device, dst device) edge, every local row can emit up to
    ``budget`` messages toward each of its peer slots on that edge.
    Sizing ``xbudget`` here makes ``dropped_xlane`` structurally zero —
    the precondition for the bit-exact sharded/single-device parity
    gate (a lane drop has no single-device analogue).  Topologies that
    accept lossy cross traffic (raft-safe) may pass less."""
    G = tables.dest_dev.shape[0]
    gl = G // n_devices
    worst = 1
    blocks = tables.dest_dev.reshape(n_devices, gl, -1)
    for s in range(n_devices):
        for d in range(n_devices):
            if d == s:
                continue
            worst = max(worst, int((blocks[s] == d).sum()) * budget)
    return worst


# packed cross-lane row layout (single source of truth for pack/unpack):
# the 9 wire columns, then sender replica id, destination local row,
# destination region rank, region slot b, found flag, then E entry
# terms and E entry cc bits.
_X_WIRE = (
    F_MTYPE, F_TERM, F_LOG_TERM, F_LOG_INDEX, F_COMMIT,
    F_REJECT, F_HINT, F_HINT_HIGH, F_N_ENTRIES,
)
_XI_FROM = len(_X_WIRE)
_XI_LOC = _XI_FROM + 1
_XI_RANK = _XI_FROM + 2
_XI_B = _XI_FROM + 3
_XI_FOUND = _XI_FROM + 4
_X_KF = _XI_FROM + 5  # ent_term starts here; width = _X_KF + 2*E


def cross_exchange(
    state: DeviceState,
    out: DeviceOut,
    inbox: Inbox,
    dest_local: jnp.ndarray,
    dest_dev: jnp.ndarray,
    rank_in_dest: jnp.ndarray,
    *,
    axis: str,
    n_dev: int,
    budget: int,
    xbudget: int,
    base: int,
    suppress: Optional[jnp.ndarray] = None,
) -> Tuple[Inbox, CrossStats]:
    """The device-to-device collective lane (runs INSIDE shard_map).

    Messages whose destination replica lives on another device are
    packed into a fixed per-edge buffer ([n_dev, xbudget, KT] int32 —
    the same fixed-budget discipline as the routed regions), exchanged
    with ``lax.ppermute`` (one hop per ring shift; n_dev-1 permutes of a
    tiny buffer), and scattered into the SAME inbox region slots the
    intra-device router would have used — ``base + rank*budget + b`` —
    so a sharded round's assembled inbox is bit-identical to the
    single-device router's (the parity contract of
    tests/test_multichip.py).  Region-slot identity is safe because a
    (dest row, rank) region has exactly ONE sender, and that sender is
    on exactly one device: a region is local-fed XOR lane-fed.

    Overflow (per-sender rank >= budget, per-edge slot >= xbudget) is
    DROPPED and counted — raft tolerates arbitrary message loss, same
    contract as the intra-device router.  Zero host transfers: pure
    int32 device math + ppermute.
    """
    G, O, _ = out.buf.shape
    P, W, B, E = state.P, state.W, budget, inbox.E
    M = inbox.M
    D, XB = n_dev, xbudget
    if D <= 1:
        zero = jnp.zeros((), I32)
        return inbox, CrossStats(zero, zero, zero, zero, zero)
    me = jax.lax.axis_index(axis)

    buf = out.buf
    mtype = buf[:, :, F_MTYPE]
    to = buf[:, :, F_TO]
    n_ent = buf[:, :, F_N_ENTRIES]
    log_index = buf[:, :, F_LOG_INDEX]
    log_term = buf[:, :, F_LOG_TERM]
    valid = jnp.arange(O)[None, :] < out.count[:, None]
    if suppress is not None:
        valid = valid & ~suppress[:, None]
    hits = (
        (state.peer_id[:, None, :] == to[:, :, None])
        & (to[:, :, None] != 0)
        & (state.peer_id[:, None, :] != 0)
    )  # [G, O, P]
    found = jnp.any(hits, axis=2)

    def at_pstar(tab):  # [G, P] table value at the hit slot, [G, O]
        return jnp.sum(jnp.where(hits, tab[:, None, :], 0), axis=2)

    xdev = at_pstar(dest_dev)
    xloc = at_pstar(dest_local)
    xrank = at_pstar(rank_in_dest)
    # deliverability mirrors route(): REPLICATE payload must be ring-
    # resident on the sender (below-ring HOST-FIXUP markers excluded),
    # forwarded PROPOSE never rides the device (payload is host-only)
    is_repl = mtype == MT_REPLICATE
    carries = is_repl & (n_ent > 0)
    win_lo = jnp.maximum(state.first_index, state.last_index - (W - 1))
    marker = is_repl & (log_index > 0) & (log_term == 0)
    ring_ok = ~carries | (
        (log_index + 1 >= win_lo[:, None])
        & (log_index + n_ent <= state.last_index[:, None])
        & ~marker
    )
    remote = found & (xdev >= 0) & (xdev != me)
    routable = valid & remote & (mtype != MT_PROPOSE)
    deliverable = routable & ring_ok
    # per-(sender, peer-slot) region rank b — the SAME counting the
    # single-device router applies (all of a (g, p) pair's messages go
    # to one destination device, so the two counts can never interleave)
    oh = (hits & deliverable[:, :, None]).astype(I32)
    k_excl = jnp.cumsum(oh, axis=1) - oh
    b_of = jnp.sum(jnp.where(hits, k_excl, 0), axis=2)  # [G, O]
    in_b = b_of < B
    sendable = deliverable & in_b
    # per-edge lane slot q (fixed budget XB per destination device)
    N = G * O
    edge = (
        (xdev[:, :, None] == jnp.arange(D)[None, None, :])
        & sendable[:, :, None]
    ).reshape(N, D)
    q_excl = jnp.cumsum(edge.astype(I32), axis=0) - edge
    in_q = edge & (q_excl < XB)
    # pack one [KT] row per message: wire fields + lane metadata + the
    # REPLICATE payload (terms/cc) reconstructed from the sender's ring
    wm = W - 1
    ents_t = []
    ents_c = []
    for e in range(E):
        pos = jnp.clip(log_index + 1 + e, 0, None) & wm  # [G, O]
        selw = pos[:, :, None] == jnp.arange(W)[None, None, :]
        has_e = carries & (e < n_ent)
        et = jnp.sum(
            jnp.where(selw, state.ring_term[:, None, :], 0), axis=2
        )
        ec = jnp.sum(jnp.where(selw, state.ring_cc[:, None, :], 0), axis=2)
        ents_t.append(jnp.where(has_e, et, 0))
        ents_c.append(jnp.where(has_e, ec, 0))
    from_g = jnp.broadcast_to(state.replica_id[:, None], (G, O))
    fields = jnp.stack(
        [buf[:, :, c] for c in _X_WIRE]
        + [from_g, xloc, xrank, b_of, sendable.astype(I32)]
        + ents_t + ents_c,
        axis=2,
    ).reshape(N, -1)  # [N, KT]
    KT = fields.shape[1]
    # xbuf[d, xb] = the message holding lane slot xb of edge me->d
    sel = (
        in_q[:, :, None] & (q_excl[:, :, None] == jnp.arange(XB))
    )  # [N, D, XB]
    xbuf = jnp.matmul(
        sel.astype(I32).transpose(1, 2, 0).reshape(D * XB, N), fields
    ).reshape(D, XB, KT)
    # ring exchange: shift s hands each device the buffer its neighbor
    # s hops back packed for it — D-1 ppermutes of [XB, KT] int32
    recv_parts = []
    for shift in range(1, D):
        dst_slice = jax.lax.dynamic_index_in_dim(
            xbuf, (me + shift) % D, axis=0, keepdims=False
        )
        perm = [(i, (i + shift) % D) for i in range(D)]
        recv_parts.append(jax.lax.ppermute(dst_slice, axis, perm=perm))
    recv = jnp.concatenate(recv_parts, axis=0)  # [(D-1)*XB, KT]
    R = recv.shape[0]
    ok = recv[:, _XI_FOUND] != 0
    row = recv[:, _XI_LOC]
    slot = base + recv[:, _XI_RANK] * B + recv[:, _XI_B]
    # one-hot scatter into the (guaranteed-empty) region slots: no two
    # received messages share (row, slot) — single sender per region,
    # distinct b per sender — so the adds never collide, and the local
    # router left lane-fed regions zero (their dest_row is -1 locally)
    selr = (
        ok[:, None, None]
        & (row[:, None, None] == jnp.arange(G)[None, :, None])
        & (slot[:, None, None] == jnp.arange(M)[None, None, :])
    )  # [R, G, M]

    def put(col):
        return jnp.sum(
            jnp.where(selr, recv[:, col][:, None, None], 0), axis=0
        ).astype(I32)

    wire_at = {c: i for i, c in enumerate(_X_WIRE)}
    ent_t = jnp.sum(
        jnp.where(
            selr[:, :, :, None],
            recv[:, None, None, _X_KF:_X_KF + E],
            0,
        ),
        axis=0,
    ).astype(I32)
    ent_c = jnp.sum(
        jnp.where(
            selr[:, :, :, None],
            recv[:, None, None, _X_KF + E:_X_KF + 2 * E],
            0,
        ),
        axis=0,
    ).astype(I32)
    inbox = Inbox(
        mtype=inbox.mtype + put(wire_at[F_MTYPE]),
        from_id=inbox.from_id + put(_XI_FROM),
        term=inbox.term + put(wire_at[F_TERM]),
        log_term=inbox.log_term + put(wire_at[F_LOG_TERM]),
        log_index=inbox.log_index + put(wire_at[F_LOG_INDEX]),
        commit=inbox.commit + put(wire_at[F_COMMIT]),
        reject=inbox.reject + put(wire_at[F_REJECT]),
        hint=inbox.hint + put(wire_at[F_HINT]),
        hint_high=inbox.hint_high + put(wire_at[F_HINT_HIGH]),
        n_entries=inbox.n_entries + put(wire_at[F_N_ENTRIES]),
        ent_term=inbox.ent_term + ent_t,
        ent_cc=inbox.ent_cc + ent_c,
    )
    stats = CrossStats(
        sent=jnp.sum(in_q, dtype=I32),
        delivered=jnp.sum(ok, dtype=I32),
        dropped_budget=jnp.sum(deliverable & ~in_b, dtype=I32),
        dropped_xlane=jnp.sum(
            sendable & ~jnp.any(in_q.reshape(G, O, D), axis=2), dtype=I32
        ),
        dropped_ring=jnp.sum(routable & ~ring_ok, dtype=I32),
    )
    return inbox, stats


def make_sharded_round(  # mesh-hot
    mesh,
    *,
    M: int,
    E: int,
    out_capacity: int,
    budget: int,
    xbudget: int,
    base: int,
    propose_leaders: bool = False,
    propose_n: int = 1,
    rounds: int = 1,
):
    """Build the jitted shard_map'd consensus round for a 1-D groups
    mesh: per-device step over the local G-slice, intra-device routing
    EXACTLY as the single-device router (``route`` over the mesh
    tables' local view), and cross-device raft traffic on the
    ``cross_exchange`` collective lane — zero host transfers in the
    steady loop (pinned by the jaxcheck transfer audit over
    ``registry.mesh_entry_points``).

    ``rounds > 1`` fuses consecutive rounds INSIDE the shard-mapped
    program (the mesh form of :func:`fused_rounds`): the ppermute
    collective lane fires BETWEEN fused rounds — cross-chip raft
    traffic sent in round k is scattered into round k+1's inbox
    regions before that round steps, never deferred to the end of the
    wave — so a sharded fused wave is bit-exact with ``rounds``
    sequential sharded rounds AND with the single-device
    ``fused_rounds`` over the same global topology
    (tests/test_pipeline.py mesh parity).

    Returns ``round_fn(state, inbox, dest_local, dest_dev, rank) ->
    (state', inbox', route_stats [D*rounds, 6], lane_stats
    [D*rounds, 7])`` where all row-axis operands are sharded over the
    mesh (jit re-shards uncommitted inputs automatically) and the
    per-device stats lanes are: RouteStats order for the local router,
    then [sent, delivered, dropped_budget, dropped_xlane, dropped_ring,
    escalated, rows_live] for the lane/step, one row per (device,
    round) — the per-device split (``rounds=1``, the default, keeps
    the historical [D, 6]/[D, 7] shape).
    """
    import jax as _jax

    from jax import shard_map as _shard_map
    from jax.sharding import PartitionSpec as _PS

    if len(mesh.axis_names) != 1:
        raise ValueError("groups mesh must be one-dimensional")
    axis = mesh.axis_names[0]
    D = mesh.size
    from . import kernel as K

    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")

    def _local_round(state, inbox, dest_local, dest_dev, rank):
        me = jax.lax.axis_index(axis)
        local_dest = jnp.where(
            dest_dev == me, dest_local, jnp.int32(-1)
        )
        stats_l = []
        lane_l = []
        # unrolled fused rounds: the collective lane runs INSIDE the
        # per-round tail, so cross-chip traffic from round k feeds
        # round k+1's step — never batched to the end of the wave
        for _ in range(rounds):
            new_state, out = K.step(
                state, inbox, out_capacity=out_capacity
            )
            esc = out.escalate != 0
            n_esc = jnp.sum(esc, dtype=I32)
            keep = ~esc

            def sel(a, b, keep=keep):
                m = keep.reshape((-1,) + (1,) * (a.ndim - 1))
                return jnp.where(m, b, a)

            state2 = jax.tree.map(sel, state, new_state)
            prefill = make_prefill(
                state2, M, E,
                propose_leaders=propose_leaders, propose_n=propose_n,
            )
            next_inbox, stats, _delivered = route(
                state2, out, local_dest, rank,
                M=M, E=E, budget=budget, base=base,
                base_inbox=prefill, suppress=esc,
            )
            next_inbox, xstats = cross_exchange(
                state2, out, next_inbox, dest_local, dest_dev, rank,
                axis=axis, n_dev=D, budget=budget, xbudget=xbudget,
                base=base, suppress=esc,
            )
            rows_live = jnp.sum(keep, dtype=I32)
            stats_l.append(jnp.stack(list(stats)))
            lane_l.append(jnp.stack(list(xstats) + [n_esc, rows_live]))
            state, inbox = state2, next_inbox
        # [rounds, 6]/[rounds, 7] per shard -> [D*rounds, *] global
        return state, inbox, jnp.stack(stats_l), jnp.stack(lane_l)

    return _jax.jit(
        _shard_map(
            _local_round,
            mesh=mesh,
            in_specs=(
                _PS(axis), _PS(axis), _PS(axis), _PS(axis), _PS(axis),
            ),
            out_specs=(_PS(axis), _PS(axis), _PS(axis), _PS(axis)),
            # see make_step_sharded: all specs sharded, nothing to check
            check_vma=False,
        )
    )
