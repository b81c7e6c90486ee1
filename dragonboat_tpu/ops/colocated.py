"""Colocated-cluster mode: one device state shared by several NodeHosts.

The reference's step workers hand every outbound message to the
transport even when the peer replica lives in the same process
(reference: engine.go stepWorkerMain -> transport.Send [U]).  The
``VectorStepEngine`` inherits that shape: each message round-trips
device -> host decode -> transport -> host encode -> device.  When a
whole cluster is colocated on one chip (multiple NodeHosts in one
process — the standard test topology, and the production topology
for BASELINE configs 2-4), that detour is the scaling bottleneck.

``ColocatedEngineGroup`` is the product configuration that removes it:

    group = ColocatedEngineGroup(capacity=64, P=5, budget=2)
    for each NodeHost config:
        cfg.expert.step_engine_factory = group.factory

Every member NodeHost's step engine becomes a facade over ONE shared
``ColocatedVectorEngine``: all replicas live in one device state, and
``ops/route.py`` scatters each step's outbox straight into co-located
peers' inbox regions — elections, replication and commit advance run
device-side, exactly like the consensus benchmark, while off-device
peers (and host-only message classes) fall back to the per-host
transport unchanged (route's ``delivered`` mask tells the host which
messages it still owns).

Payload reconstruction across replicas: device-routed REPLICATE carries
only (term, is-config-change) per entry — the cmd bytes never leave the
sending host.  Colocation makes the fix cheap: every stamped append is
published to a shared per-shard entry cache (bounded by the ring
lifetime), and a receiving replica's merge pulls payloads from the
cache by (index, term).  A miss on a non-leader row fail-stops the
replica (see ``VectorStepEngine._merge_appends``) — silent empty
entries would diverge the SM.

Concurrency: the colocated step holds the core lock end-to-end.  Member
NodeHosts keep their own ExecEngines, apply workers, LogDBs and
transports; only the step stage is fused.  A launch triggered by any
member steps EVERY resident row (routed traffic may target any of
them), and updates are persisted to each node's own LogDB before its
messages are dispatched (the reference's save -> send -> apply order).
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import itertools
import operator
import threading
import time as _time
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
# resolved once, here: the launch loop opens a dozen regions a launch
from jax.profiler import TraceAnnotation as _TraceAnnotation

from ..analysis import jitcheck
from ..engine.execengine import APPLY_TOTALS, IStepEngine
from . import hostplane
from ..logger import get_logger
from ..node import StepInputs
from ..pb import Entry
from ..raft.raft import RaftRole
from ..request import HOST_TOTALS, gc_tables
from . import kernel as K
from . import sync as S
from .engine import (
    VectorStepEngine,
    _shift_msg_indexes,
    _F_ANY_LIVE,
    _F_APPEND,
    _F_COUNT,
    _F_ESC,
    _F_NEED_SS,
    _F_PEERS_BEHIND,
    _R_APPEND_LO,
    _R_BARRIER_IDX,
    _R_BARRIER_TERM,
    _R_COMMIT,
    _R_COUNT,
    _R_LEADER,
    _R_ROLE,
    _R_TERM,
    _R_VOTE,
    _R_LAST,
    _ROLE_OF,
    _bucket,
    _place_rows,
    _pos_map,
    _build_idx4,
    _detail_width,
    _fetch_detail_vals,
    _gather_detail,
    _gather_detail_vals,
    _gather_vals,
    _row_mask,
    _vals_of,
    _split_detail,
    N_FIELDS_BUF,
    N_VALS,
    _summarize_flags,
    _tick_bookkeeping,
    _pad_idx,
    _set_remote_snapshot,
)
from .types import (
    ROLE_LEADER as _ROLE_LEADER_I,
    U_COMMIT,
    U_LEADER,
    U_LOST_LEAD,
    U_ROLE,
    U_STATE,
)
from .route import build_route_tables, route
from .types import (
    APPEND_LO_NONE,
    I32,
    MT_TICK,
    SLOT_UNUSED as SLOT_UNUSED_I,
    Inbox,
    PackedInbox,
    PackedOut,
    PackedState,
    make_inbox_packed,
    make_state_packed,
    pack_inbox,
    pack_out,
    pack_state,
    unpack_inbox,
    unpack_out,
    unpack_state,
)

_log = get_logger("engine")
_perf = _time.perf_counter
_cpu = _time.thread_time
# the launch clock reads the thread's processor clock at a switch only
# when this much wall time has passed since its last read (and always at
# a step call's first switch): on the chip machine a read is a system
# call of ~5.5 us, and a launch makes ~120 switches, most of them
# microseconds apart (PERF.md §5, PR 37).  A shorter stretch is charged
# with the phase left at the next read; a call's last one is not charged
_CPU_READ_MIN_S = 0.00025

# the launch clock's phases, each with its processor-time sibling
# (t_<phase>_ms -> cpu_<phase>_ms); t_between_ms has none: between step
# calls no launch thread is running
_CPU_OF = {
    f"t_{p}_ms": f"cpu_{p}_ms" for p in (
        "coalesce", "plan", "upload", "encode", "dispatch", "dev_blob",
        "merge", "detail", "updates", "persist", "wake", "misc",
    )
}
_STOPPED = operator.attrgetter("stopped")
# the coalesce scan's floor: inside it at most one launch goes without a
# scan (_coalesce); at step-call periods of half of it the input hint
# pulls nothing (_pull_hinted)
_SCAN_FLOOR_S = 0.2

# -- double-buffered generations (the launch pipeline) -----------------
# Generations in flight at once.  2 double-buffers: while generation
# N's blob readback is in flight, generation N+1 assembles, uploads and
# dispatches — the donated-buffer program chain permits it, and where
# a device->host sync has a latency floor the readback overlaps the
# next launch's host work.  1 is the serial loop (dispatch, sync,
# merge, repeat), the reference the pipelined path is tested against.
_PIPE_DEPTH_DEFAULT = 2
# Simulated link latency, ms: a readback's data is not considered
# landed until this long after the D2H copy was REQUESTED
# (copy_to_host_async), as on a remote device.  0.0 is the real
# machine: on a local v5e a small readback lands in under a millisecond
# (PERF.md).
_SYNC_FLOOR_MS_DEFAULT = 0.0
# Consecutive consensus rounds a routable generation chains device-side
# before its ONE readback (the fused commit wave).  3 is one full
# propose -> replicate/ack -> commit/deliver sequence: a quiet-path
# proposal commits in one launch + one readback instead of three of
# each.  1 is the single-round launch loop, the reference the fused
# path is tested against.
_FUSED_ROUNDS_DEFAULT = 3

# fast-lane invalidation margin: re-validate a row's int32 headroom via
# the full plan well before the hard 2^31 ceiling (margin >> M*E and
# any per-launch term burst)
_LIM_SOFT = 2**31 - 2**24


# per-launch [G, 4] lane assignments of the launch's [G] host inputs.
# They cross as part of the launch's ONE upload (see _pack_launch): on
# the local chip a transfer costs no link round trip, but every device
# call gives the interpreter lock away, and with ~90 threads in the
# process the launch thread waits up to a switch interval to run again
# (PERF.md section 6) — the cost is a call's, not a byte's
_C_ALIVE, _C_BATCH, _C_PROP, _C_TICKS = range(4)


def _combo_np(tick_counts, alive_np, batch_gs, prop_gs) -> np.ndarray:
    """The [G, 4] per-launch host inputs (alive, batch membership,
    proposal rows, fused tick counts), one column each."""
    combo = np.zeros((len(tick_counts), 4), np.int32)
    combo[:, _C_TICKS] = tick_counts
    combo[:, _C_ALIVE] = alive_np
    combo[batch_gs, _C_BATCH] = 1
    combo[prop_gs, _C_PROP] = 1
    return combo


def _pack_launch(G: int, M: int, E: int, tick_counts, alive_np, batch_gs,
                 prop_gs, sparse) -> Tuple[np.ndarray, int]:
    """Everything the host tells the device about one launch, as ONE
    flat int32 vector (and the bucket ``NSB`` it is packed at):

      [0:4G]            the [G, 4] combo (_combo_np), row-major
      [4G:5G]           pos: row g takes dense row pos[g], -1 = none
      [5G:5G+NSB*R]     the dense rows' inboxes (S.encode_inbox_np),
                        R = S.inbox_row_ints(M, E) ints a row

    ``sparse`` is the ``(g, Message list)`` rows that upload a dense
    inbox; NSB = _bucket(len(sparse)), 0 with none.  Rows past
    len(sparse) stay zero: no pos points at them."""
    nsb = _bucket(len(sparse)) if sparse else 0
    R = S.inbox_row_ints(M, E)
    flat = np.zeros((5 * G + nsb * R,), np.int32)
    flat[:4 * G] = _combo_np(
        tick_counts, alive_np, batch_gs, prop_gs
    ).reshape(-1)
    flat[4 * G:5 * G] = _pos_map(G, [g for g, _ in sparse])
    if sparse:
        _, overflow = S.encode_inbox_np(
            [m for _, m in sparse], M, E,
            out=flat[5 * G:].reshape(nsb, R),
        )
        assert not overflow, (
            "planner let oversized rows through: "
            f"{[sparse[i][0] for i in overflow]}"
        )
    return flat, nsb


@jax.jit
def _assemble_inbox(host: Inbox, pending: Inbox, alive: jnp.ndarray) -> Inbox:
    """Concatenate the ROUTED regions first, then the host-encoded
    slots, zeroing rows that are not device-authoritative (dirty /
    detached — a stale device row receiving traffic could double-vote).

    Routed-first is the scalar replay order (received messages before
    proposals/reads/ticks): routed traffic IS received messages, and
    the host region ends with the fused tick slot.  With the old
    host-first order a candidate's tick slot could re-fire its election
    BEFORE counting the vote responses already sitting in its routed
    region — with multi-tick fusion (+timeout//2 per launch) that
    re-campaign loop stalled whole-cluster elections."""

    def cat(h, p):
        x = jnp.concatenate([p, h], axis=1)  # pending | host
        m = alive.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, 0)

    return Inbox(*(cat(getattr(host, f), getattr(pending, f))
                   for f in Inbox._fields))


@functools.partial(jax.jit, static_argnames=("out_capacity",),
                   donate_argnums=(2,))
def _assemble_and_step(state: PackedState, host: PackedInbox,
                       pending: PackedInbox, combo, *, out_capacity: int):
    """Fused inbox assembly + kernel step in ONE program, with the
    pending inbox declared DONATED: a fast launch cadence at 65k-row
    geometry once out-allocated the device (r5 finding —
    RESOURCE_EXHAUSTED mid-election); fusing avoids materializing the
    assembled inbox as a host-held buffer, and the donation invites the
    runtime to reuse the allocation.  No output has an inbox's shape, so
    jax 0.9.0 reports the donation "not usable" and leaves the buffer
    alone, on the TPU as on the CPU (chip run, PR 21): the callers
    still treat ``pending`` as consumed.  ``host`` is NOT donated:
    rounds 2..K of a fused wave all read one resident all-zero host
    inbox.  ``combo`` is the [G, 4] lane block of the launch's upload
    (see _C_*); the alive lane masks rows.  State, inboxes and outputs
    cross in the packed forms (types.PackedState)."""
    full = _assemble_inbox(unpack_inbox(host), unpack_inbox(pending),
                           combo[:, _C_ALIVE] != 0)
    new, out = K.step(unpack_state(state), full, out_capacity=out_capacity)
    return pack_state(new), pack_out(out)


@functools.partial(jax.jit, static_argnames=("PB", "E", "budget"),
                   donate_argnums=(1,))
def _route_step(old: PackedState, new: PackedState, out: PackedOut, dest,
                rank, combo, *, PB: int, E: int, budget: int):
    """_route_step_body over the packed forms (types.PackedState)."""
    merged, regions, stats, packed, flags = _route_step_body(
        unpack_state(old), unpack_state(new), unpack_out(out), dest, rank,
        combo, PB=PB, E=E, budget=budget,
    )
    return pack_state(merged), pack_inbox(regions), stats, packed, flags


def _route_step_body(old_state, new_state, out, dest, rank, combo,
                     *, PB: int, E: int, budget: int):
    """Post-launch tail: discard escalated rows' effects, route the
    outboxes into the next launch's pending regions (width P*budget,
    base=0 — host slots are prepended at the next assemble), and compute
    the per-row flag word + bit-packed delivered mask so the host reads
    back O(1)-width arrays instead of the full summary/delivered
    matrices (multi-MB per launch)."""
    esc = out.escalate != 0
    merged = jax.tree.map(
        lambda a, b: jnp.where(_row_mask(~esc, a), b, a), old_state, new_state
    )
    regions, stats, delivered = route(
        merged, out, dest, rank,
        M=PB, E=E, budget=budget, base=0,
        suppress=esc, dest_alive=combo[:, _C_ALIVE] != 0,
    )
    flags = _summarize_flags(old_state, merged, out)
    # colocated override of _F_COUNT: only rows with UNdelivered outbox
    # messages need host decode — a leader whose heartbeats/votes all
    # scattered into peer rows has nothing host-visible, and during an
    # election storm that is nearly every row (the buf gather would
    # otherwise be a ~44 MB readback at 65k rows)
    G, O = delivered.shape
    valid = jnp.arange(O)[None, :] < out.count[:, None]
    undeliv = jnp.any(valid & ~delivered, axis=1)
    flags = (flags & ~jnp.int32(_F_COUNT)) | jnp.where(
        undeliv, _F_COUNT, 0
    ).astype(I32)
    nwords = (O + 31) // 32
    shift = jnp.arange(O, dtype=jnp.uint32) % 32
    word = jnp.arange(O) // 32
    bits = jnp.where(delivered, jnp.uint32(1) << shift, jnp.uint32(0))
    packed = jnp.zeros((G, nwords), jnp.uint32)
    for w in range(nwords):  # nwords is static and tiny (O<=64 -> <=2)
        packed = packed.at[:, w].set(
            jnp.sum(jnp.where(word[None, :] == w, bits, 0), axis=1,
                    dtype=jnp.uint32)
        )
    return merged, regions, jnp.stack(list(stats)), packed, flags


# deterministic select-capacity ladder (clamped to G at use): free-form
# adaptive capacities keyed a fresh XLA program per distinct tuple and
# the mid-run compiles froze the launch pipeline (r5 finding: phase C
# commits arrived ~25 s late).  The fixed tiers are warmed at startup,
# live in the persistent cache, and any count beyond the big tier falls
# back to the exact host-side gather for that launch.
_SEL_TIERS = (
    {"b": 16, "sl": 64, "n": 8, "a": 64, "s": 1024},
    {"b": 64, "sl": 1024, "n": 32, "a": 1024, "s": 16384},
    {"b": 256, "sl": 4096, "n": 64, "a": 4096, "s": 65536},
    # storm tier for scale geometries (mass-start elections append the
    # become-leader barrier on tens of thousands of rows per launch);
    # ring rows are 2W ints and vals rows 10, so even 32k/256k rows
    # transfer in ~100s of ms — the exact bytes the r5 two-sync path
    # moved for the same storms, minus its extra round trips
    {"b": 1024, "sl": 8192, "n": 256, "a": 32768, "s": 1 << 18},
)


@functools.partial(
    jax.jit,
    static_argnames=("CAP_B", "CAP_SL", "CAP_N", "CAP_A", "CAP_S",
                     "HOST_OFF"),
)
def _select_and_blob(merged: PackedState, out: PackedOut, stats, packed,
                     flags, combo, **caps):
    """_select_and_blob_body over the packed forms (types.PackedState)."""
    return _select_and_blob_body(
        unpack_state(merged), unpack_out(out), stats, packed, flags, combo,
        **caps,
    )


def _select_and_blob_body(merged, out, stats, packed, flags, combo,
                          *, CAP_B: int, CAP_SL: int, CAP_N: int,
                          CAP_A: int, CAP_S: int, HOST_OFF: int):
    """Device-side row selection + detail/vals gather + split-blob
    packing — the launch's one commit-proving readback, as a (head,
    detail) pair of int32 vectors whose D2H copies ride in parallel.

    Every separate sync is a host round trip of its own; the r5 launch
    paid ~5 (flags, stats, delivered, detail, vals).  This program
    mirrors the
    host's row-set computation (live/buf/append/need/slot/sum) from the
    flag word, compacts each set with a stable argsort (selected rows
    first, ascending), gathers each section for its own capacity, and
    packs everything the host reads per launch into TWO int32 vectors:

    * the HEAD carries the flags/delivered prefix, route stats, section
      counts, the selected row ids and the per-row VALUES block — i.e.
      everything that PROVES a proposal's commit (committed/term/role
      per row).  The pipeline completes futures from this, the
      earliest commit-proving sync, without waiting for the detail
      payload to land and merge.
    * the DETAIL carries the heavy sections (outbox bytes, slot
      bookkeeping, need rows, ring windows) the append/message merge
      needs.  Both copies are requested together at dispatch, so on a
      latency-floor link they arrive for one round trip — the head is
      simply parsed (and acted on) first, and a generation whose
      sections are all empty never reads the detail at all.

    Counts above the static capacities are reported so the host can
    fall back to an exact multi-sync gather (rare; it then raises its
    capacity floors).

    Capacities are PER SECTION because their per-row widths differ
    wildly: one buf row is O*N_FIELDS ints (352 at O=32) while a slot
    row is M*(2+E) and a vals row is 10 — a shared capacity padded the
    heavy buf section to the proposal-row cardinality (~4 MB/launch at
    1k shards, the whole launch budget after the sync collapse).

    The slot sections ship only the HOST-region columns (HOST_OFF =
    P*budget onward): proposals ride host slots exclusively — forwarded
    PROPOSE is never device-routed — so the routed-region columns are
    always SLOT_UNUSED/0 and the host re-pads them for free.

    Head layout (all int32):
      [0:G]               flags
      [G:G+G*nw]          delivered bits (bitcast u32)
      [+6]                route stats
      [+5]                counts: n_buf, n_slot, n_need, n_append, n_sum
      [+CAP_B]            row ids: buf
      [+CAP_SL]           row ids: slot
      [+CAP_N]            row ids: need
      [+CAP_A]            row ids: append
      [+CAP_S]            row ids: sum
      [+CAP_S*N_VALS]     values
    Detail layout (all int32):
      [0:CAP_B*O*NF]      out.buf rows
      [+CAP_SL*M]         slot_base (host cols) | [+CAP_SL*M] slot_term
      [+CAP_SL*M*E]       ent_drop (host cols)
      [+CAP_N*P]          need rows
      [+CAP_A*W]          ring_term | [+CAP_A*W] ring_cc
    """
    G = flags.shape[0]
    alive = combo[:, _C_ALIVE] != 0
    batch_mask = combo[:, _C_BATCH] != 0
    prop_mask = combo[:, _C_PROP] != 0
    esc = (flags & _F_ESC) != 0
    anylive = (flags & _F_ANY_LIVE) != 0
    # the host's live set: batch rows + resident alive rows with
    # any-live flags, minus escalations
    live = (batch_mask | (alive & anylive)) & ~esc
    buf_sel = live & ((flags & _F_COUNT) != 0)
    append_sel = live & ((flags & _F_APPEND) != 0)
    need_sel = live & ((flags & _F_NEED_SS) != 0)
    slot_sel = prop_mask & ~esc
    sum_sel = live & (anylive | slot_sel)

    def pick(sel, cap):
        order = jnp.argsort(jnp.where(sel, 0, 1), stable=True)
        return (
            jax.lax.slice_in_dim(order, 0, cap).astype(I32),
            jnp.sum(sel, dtype=I32),
        )

    rows_buf, n_buf = pick(buf_sel, CAP_B)
    rows_slot, n_slot = pick(slot_sel, CAP_SL)
    rows_need, n_need = pick(need_sel, CAP_N)
    rows_append, n_append = pick(append_sel, CAP_A)
    rows_sum, n_sum = pick(sum_sel, CAP_S)
    vals = _vals_of(merged, out, rows_sum)          # [CAP_S, N_VALS]
    head = jnp.concatenate([
        flags,
        jax.lax.bitcast_convert_type(packed, jnp.int32).reshape(-1),
        stats.astype(I32),
        jnp.stack([n_buf, n_slot, n_need, n_append, n_sum]),
        rows_buf,
        rows_slot,
        rows_need,
        rows_append,
        rows_sum,
        vals.reshape(-1),
    ])
    detail = jnp.concatenate([
        out.buf[rows_buf].reshape(-1),
        out.slot_base[rows_slot][:, HOST_OFF:].reshape(-1),
        out.slot_term[rows_slot][:, HOST_OFF:].reshape(-1),
        out.ent_drop[rows_slot][:, HOST_OFF:].reshape(-1),
        out.need_snapshot[rows_need].reshape(-1),
        merged.ring_term[rows_append].reshape(-1),
        merged.ring_cc[rows_append].reshape(-1),
    ])
    return head, detail


@jax.jit
def _zero_inbox_rows(inbox: PackedInbox, mask) -> PackedInbox:
    """Zero the inbox rows where ``mask`` ([G] bool) — mask-select, not
    a data-dependent scatter (serial on TPU; see _scatter_rows)."""
    return jax.tree.map(
        lambda a: jnp.where(_row_mask(mask, a, 1), 0, a), inbox
    )


@functools.partial(jax.jit, static_argnames=("G", "M", "E", "NSB"))
def _host_inbox(flat, *, G: int, M: int, E: int, NSB: int):
    """Unpack one launch's upload (_pack_launch's layout) ON DEVICE into
    ``(combo, host inbox)``: the one program of the encode phase.

    At scale, nearly every row's host region is exactly one
    count-carrying LOCAL_TICK slot — uploading the dense [G, M(, E)]
    inbox arrays is ~28 MB per launch at 65k rows; the tick column of
    the combo is 256 KB.  So the base is built here from the tick
    counts, and the ``NSB`` rows with real host slots (wire messages,
    proposals, reads, tick-with-read-hint) are placed over it by the
    shared pos-map gather-select (see engine._place_rows).  NSB = 0 is
    the launch with no such row.  The host inbox leaves packed
    (types.PackedInbox)."""
    combo = flat[:4 * G].reshape(G, 4)
    tick_counts = combo[:, _C_TICKS]
    z = jnp.zeros((G, M), I32)
    ze = jnp.zeros((G, M, E), I32)
    has = tick_counts > 0
    host = Inbox(
        mtype=z.at[:, 0].set(jnp.where(has, MT_TICK, 0)),
        from_id=z,
        term=z,
        log_term=z,
        log_index=z.at[:, 0].set(tick_counts),
        commit=z,
        reject=z,
        hint=z,
        hint_high=z,
        n_entries=z,
        ent_term=ze,
        ent_cc=ze,
    )
    if NSB:
        pos = flat[4 * G:5 * G]
        sub = S.unpack_inbox(flat[5 * G:].reshape(NSB, -1), M, E)
        host = Inbox(*(_place_rows(h, s, pos) for h, s in zip(host, sub)))
    return combo, pack_inbox(host)


def _lane_inputs(lane, i: int) -> StepInputs:
    """The ``StepInputs`` of position ``i`` of a tick lane, for the
    slow paths that need ONE row's inputs back (and the oracle)."""
    return StepInputs(ticks=lane.ticks[i],
                      gc_ticks=lane.gc.get(lane.gs[i], 0))


def _emitting(live, pos_sum, touched) -> frozenset:
    """Of ``live``, the rows a completion can emit anything for: those
    with values this round, and those an earlier round of the wave
    touched.  Every other live row only ticked.  (Parity oracle.)"""
    return frozenset(
        g for _node, g, _si in live if pos_sum[g] >= 0 or g in touched
    )


class _InFlightGen:
    """One dispatched-but-unmerged generation of the launch pipeline.

    Holds every host-side fact the deferred merge tail needs (the
    generation's OWN inputs — the parity oracle must run against these,
    not the interleaved stream) plus the device handles the exact
    two-sync fallback gather reads.  ``merged``/``out`` pin the
    generation's buffers alive until its merge runs; with depth 2 that
    is the ISSUE's "two in-flight state handles".

    A FUSED generation (``rounds > 1``, ISSUE 15) carries one entry
    per round in ``merged``/``out``/``head_dev``/``detail_dev``: the
    wave dispatched K rounds back-to-back with every round's (head,
    detail) D2H copy requested at dispatch, so the whole wave's blobs
    share ONE readback window and the merge tail unpacks them round by
    round."""

    __slots__ = (
        "batch", "lane", "staging", "alive_np", "batch_gs", "fed",
        "prop_gs", "caps", "merged", "out", "head_dev", "detail_dev",
        "t_req", "rounds", "clocked",
    )

    def __init__(self, *, batch, lane, staging, alive_np, batch_gs, fed,
                 prop_gs, caps, merged, out, head_dev, detail_dev, t_req,
                 rounds=1):
        # the stepped rows, split in two: ``batch`` holds the ACTIVE
        # rows as (node, g, si, plan) tuples, ``lane`` the tick-only
        # rows as columns (hostplane.TickLane).  ``batch_gs`` is the
        # whole stepped set's row ids, active rows first and the lane's
        # after, as the device's _C_BATCH column and build_merge_sets
        # see it: a position past len(batch) names a lane row.  ``fed``
        # is parallel to it: the ticks each row was fed.
        self.batch = batch
        self.lane = lane
        self.staging = staging
        self.alive_np = alive_np
        self.batch_gs = batch_gs
        self.fed = fed
        self.prop_gs = prop_gs
        self.caps = caps
        self.merged = merged          # per-round list of state handles
        self.out = out                # per-round list of DeviceOut
        self.head_dev = head_dev      # per-round list of head blobs
        self.detail_dev = detail_dev  # per-round list of detail blobs
        self.t_req = t_req
        self.rounds = rounds
        # both clocks of the generation's rows advanced (the
        # completion's bookkeeping ran, or a pipeline reset did it)
        self.clocked = False

    def fed_of(self, g: int) -> int:
        """The ticks this generation fed device row ``g``, 0 for a row
        it did not step."""
        at = np.flatnonzero(self.batch_gs == g)
        return int(self.fed[at[0]]) if len(at) else 0

    def stepped_row(self, i: int) -> Tuple:
        """``(node, g, si)`` of position ``i`` of ``batch_gs``; a lane
        row's ``StepInputs`` is made here, for the one row asked for."""
        n = len(self.batch)
        if i < n:
            node, g, si, _plan = self.batch[i]
            return node, g, si
        lane = self.lane
        return lane.nodes[i - n], lane.gs[i - n], _lane_inputs(lane, i - n)


class _GcClock:
    """The core's ``gc.callbacks`` entry.  Every collection, by any
    thread, is a ``raft-gc`` region on the profiler's clock, opened and
    closed on the collecting thread; one that starts while a step call
    is open is counted with its wall time.  A collection stops every
    Python thread, so that time lies inside the phase it interrupted.
    Collections are serialised: one writer, the callback, and ``total``
    is replaced whole, for the launch thread to read in one load.  It
    holds the core weakly: a core dropped without ``close()`` leaves no
    cycle for the callback to keep alive."""

    __slots__ = ("_core", "_t0", "_ann", "total")

    def __init__(self, core):
        self._core = weakref.ref(core)
        self._t0 = None
        self._ann = None
        self.total = (0, 0.0)  # (launch_collections, t_launch_gc_ms)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = self._ann = _TraceAnnotation("raft-gc")
            ann.__enter__()
            core = self._core()
            in_call = core is not None and core._ph_key != "t_between_ms"
            self._t0 = _perf() if in_call else None
            return
        t0, ann = self._t0, self._ann
        self._t0 = self._ann = None
        if t0 is not None:
            n, ms = self.total
            self.total = (n + 1, ms + (_perf() - t0) * 1000.0)
        if ann is not None:
            ann.__exit__(None, None, None)


def _gc_unhook(clock: _GcClock) -> None:
    try:
        gc.callbacks.remove(clock)
    except ValueError:
        pass


class ColocatedVectorEngine(VectorStepEngine):
    """Shared device engine for several NodeHosts in one process.

    Do not construct directly — use ``ColocatedEngineGroup``.
    """

    def __init__(self, *, budget: int = 2, capacity: int = 64, P: int = 5,
                 W: int = 32, M: int = 8, E: int = 4, O: int = 32,
                 rebase_chunk: int = 1 << 30, device=None, mesh=None,
                 pipeline_depth: Optional[int] = None,
                 sync_floor_ms: Optional[float] = None,
                 fused_rounds: Optional[int] = None):
        self.budget = budget
        self._pending: Optional[PackedInbox] = None
        self._pending_live = False  # last route delivered > 0 messages
        self._host_shard = np.zeros((capacity,), np.int64)
        self._host_replica = np.zeros((capacity,), np.int64)
        self._host_peers = np.zeros((capacity, P), np.int64)
        self._tables_dirty = True
        self._dest_dev = None
        self._rank_dev = None
        # shard -> OrderedDict[(index, term) -> Entry]; bounded FIFO per
        # shard.  Depth must cover BOTH lifetimes an entry is needed
        # for: the device ring window (8*W) and the stamp-to-consumption
        # gap of a routed append — the receiver merges one launch after
        # the sender stamped, and a proposal storm can stamp up to ~M*E
        # entries per launch in between, evicting the referenced entry
        # from a W-sized budget (chaos finding: rare fail-stops at
        # W=8 under full-rate clients).  8*M*E covers several launches
        # of worst-case append volume.
        self._entry_cache: Dict[int, "OrderedDict[Tuple[int, int], Entry]"] = {}
        self._cache_depth = max(8 * W, 8 * M * E)
        # per-SHARD shared index base (the colocated 64-bit story):
        # routed messages carry raw int32 index lanes between rows, so a
        # per-row base would desynchronize them — instead every resident
        # row of a shard shares one W-aligned base, advanced by whole-
        # shard rebases (see _maybe_rebase_shards).  rebase_chunk is how
        # far committed may outrun the base before a rebase (tests
        # shrink it to exercise multi-rebase traffic at ordinary scale).
        self._shard_base: Dict[int, int] = {}
        self._rebase_chunk = rebase_chunk
        # shard -> committed level below which rebase attempts are
        # suppressed (set when an attempt finds no representable
        # progress, e.g. a lagging peer lane pins the candidate min)
        self._rebase_block: Dict[int, int] = {}
        # chaos/fault plug point: (shard_id, replica_id) -> partition
        # group.  Rows in different groups lose their device route (the
        # link falls back to the host transport — counted in
        # routed_dropped as dest<0 — where the usual drop hooks apply);
        # both sides keep ticking and campaigning, exactly a network
        # partition.  None = fully connected.
        self._part_fn = None
        # rate limit for the O(resident rows) coalesce scan (see
        # _coalesce); 0 = never scanned yet
        self._last_coalesce_scan = 0.0
        self._scan_cost = 0.0
        self._scan_skipped = False  # the last launch went without one
        # the step calls' period, smoothed (_pull_hinted)
        self._pull_at = 0.0
        self._period_avg = 0.0
        # adaptive device-select capacities for the single-sync launch
        # blob (see _select_and_blob): detail rows are ~2 KB each so
        # CAP_D tracks actual peaks tightly; vals rows are 40 B so
        # CAP_S can ride elections up to G cheaply
        # deterministic select-capacity tier (see _SEL_TIERS): index into
        # the warmed ladder + the consecutive-fits-lower-tier streak
        self._sel_tier = 0
        self._sel_fit_streak = 0
        # ---- launch pipeline (double-buffered generations) ----------
        # FIFO of dispatched-but-unmerged generations; the merge tail
        # runs one generation behind the device at depth 2.  The fence
        # contract (docs/PARITY.md "Pipeline safety argument"): rows
        # being evicted/escalated/detached drain this to depth 0 before
        # membership mutates — mirroring the ≤1-launch detach-race
        # argument at any depth.
        from collections import deque as _deque

        self._inflight: "_deque[_InFlightGen]" = _deque()
        # the input hint: nodes whose producers queued input since a
        # launch last took it (node._wake appends, _pull_hinted takes;
        # both GIL-atomic, so neither side locks)
        self._input_hint: "_deque" = _deque()
        self._pipeline_depth = max(
            1,
            pipeline_depth
            if pipeline_depth is not None
            else _PIPE_DEPTH_DEFAULT,
        )
        self._sync_floor_s = (
            sync_floor_ms
            if sync_floor_ms is not None
            else _SYNC_FLOOR_MS_DEFAULT
        ) / 1000.0
        # fused commit waves (ISSUE 15): K consecutive routed rounds
        # chained device-side per routable generation — propose ->
        # commit in one launch + one readback.  Non-routable
        # generations (membership mutation in sight, escalation holds,
        # save quarantine, stopping rows) fence to the single-round
        # path, extending the PR 11 pipeline fence argument unchanged.
        self._fuse_rounds = max(
            1,
            fused_rounds
            if fused_rounds is not None
            else _FUSED_ROUNDS_DEFAULT,
        )
        # deferred membership actions discovered mid-completion
        # (escalation replays, snapshot-below / save-failure evictions,
        # demotes): they mutate membership, so they run only once the
        # pipeline is drained to depth 0 — never from inside a merge.
        self._deferred: List[Tuple] = []
        self._running_deferred = False
        # nodes that registered a device read since the last plan loop
        # (_attach_messages): a launch takes them off its tick lane
        self._read_ctx_new: List = []
        # True while a generation's merge tail is executing: membership
        # mutators called from inside it (demote, save-failure evict)
        # must defer instead of fencing — a fence mid-merge would
        # complete LATER generations before this one finishes.
        self._completing = False
        # row slots freed while generations are in flight: an in-flight
        # merge still references them by id, so they re-enter _free only
        # at depth 0 (a re-attach reusing the slot mid-flight would let
        # one generation's effects merge into another replica's row)
        self._free_pending: List[int] = []
        self._last_worker_id = 0
        # the launch clock (see _phase): the counter being charged, and
        # since when, on the wall clock and on the launch thread's own
        # processor clock.  Between step calls that is t_between_ms
        self._ph_key = "t_between_ms"
        self._ph_t = self._ph_cpu_t = _perf()
        self._ph_cpu = _cpu()
        # the member NodeHosts' facades, whose apply workers' totals
        # _fold_apply folds into stats
        self._members: List["_ColocatedFacade"] = []
        # the wake lane: per row, which member's step-worker WorkReady
        # its node registered with (-1: none), so that _wake_alive
        # reaches every alive row's worker without a call a row
        self._wake_slot = np.full((capacity,), -1, np.int64)
        self._wake_ready: List = []
        super().__init__(None, capacity=capacity, P=P, W=W, M=M, E=E, O=O,
                         device=device, mesh=mesh)
        # nemesis escalations are consumed at plan time here: routed
        # regions suppress escalated rows ON device, so the base
        # engine's post-launch flag flip would desync the merged state
        self._consume_engine_fault_at_plan = True
        # loop-invariant delivered-bit unpack tables (word index and
        # in-word shift per outbox slot) — hoisted out of the merge loop
        self._dw_word = np.arange(self.O) // 32
        self._dw_shift = (np.arange(self.O) % 32).astype(np.uint32)
        self.stats.update(
            launches=0, routed_delivered=0, routed_dropped=0,
            coalesced_rows=0, shard_rebases=0,
            # rows a launch carried for the input hint alone (neither
            # the caller's nor the coalesce scan's), and for every row
            # the plan drained non-tick input from on the full path,
            # the time since the first of that input was queued
            input_rows_pulled=0, t_input_wait_ms=0.0, input_waits=0,
            # launches that took the hint and pulled nothing, their
            # step calls running past half the scan's floor
            input_hint_closed=0,
            # ---- the account of the core's wall time (ms), always on;
            # docs/OBSERVABILITY.md "Counters".  A step call's time is
            # t_launch_ms and the time between calls t_between_ms: the
            # two add up to the wall clock.  Inside a call every
            # millisecond is charged to exactly ONE phase (_phase: a
            # phase that runs inside another, as a lane persist does
            # inside the detail pass, is taken out of the outer one),
            # so the phases add up to t_launch_ms; t_misc_ms is the glue
            # between them (polling the in-flight readbacks for
            # readiness, in-flight bookkeeping, the retry drain)
            t_launch_ms=0.0, t_between_ms=0.0, t_misc_ms=0.0,
            t_coalesce_ms=0.0, t_plan_ms=0.0, t_upload_ms=0.0,
            t_encode_ms=0.0, t_dispatch_ms=0.0, t_dev_blob_ms=0.0,
            t_merge_ms=0.0, t_detail_ms=0.0, t_updates_ms=0.0,
            t_persist_ms=0.0, t_wake_ms=0.0,
            # the same account on the launch thread's own processor
            # clock (time.thread_time, read at the same switches): a
            # phase's wall less its processor time is its waits, for
            # the interpreter lock, a collection, I/O.  The twelve add
            # up to cpu_launch_ms; the mark starts over at every step
            # call, whose thread is whichever step worker holds the lock
            cpu_launch_ms=0.0, cpu_misc_ms=0.0, cpu_coalesce_ms=0.0,
            cpu_plan_ms=0.0, cpu_upload_ms=0.0, cpu_encode_ms=0.0,
            cpu_dispatch_ms=0.0, cpu_dev_blob_ms=0.0, cpu_merge_ms=0.0,
            cpu_detail_ms=0.0, cpu_updates_ms=0.0, cpu_persist_ms=0.0,
            cpu_wake_ms=0.0,
            # collections, by any thread, that started while a step
            # call was open, and their wall time (_GcClock, folded in
            # once a step call)
            launch_collections=0, t_launch_gc_ms=0.0,
            # not phases: the wait of step workers for the core lock
            # (summed over workers, so it overlaps other workers'
            # launches), and the part of t_persist_ms spent inside the
            # log database's save calls
            t_lock_wait_ms=0.0, t_wal_ms=0.0,
            # what those saves cost the WAL (ILogDB.wal_counts deltas)
            wal_appends=0, wal_bytes=0, wal_records=0,
            # batch rows whose host inbox held more than ticks
            # (proposals, host-carried messages, reads), as opposed to
            # device_rows_stepped, which counts tick-only rows too
            device_rows_active=0,
            # batch rows encoded through the tick lane (two arrays, no
            # Message objects: hostplane.encode_tick_lane); the rest of
            # device_rows_stepped went through _encode_rows one by one
            tick_lane_rows=0,
            # rows the completion tail touched in Python: the live list
            # of every round (active rows, lane rows the round's flags
            # mark, resident rows with effects), plus the rows the
            # lease pass armed or disarmed.  The tick lane's other rows
            # are clocked in one two-column loop and not counted
            completion_rows_walked=0,
            # the lease, renewed every launch (hostplane.LeaseAges):
            # armed leader rows a completion stepped with ticks, and of
            # those the ones a quorum had answered since the feed
            # (F_QUORUM_FRESH), whose lease age started over; and the
            # rows of the engine a completion anchored, at its feed or
            # at an earlier feed whose answers came in this launch
            lease_rows_armed=0, lease_rows_fresh=0, lease_rows_anchored=0,
            # the apply workers' totals over every member NodeHost,
            # folded in once a step call (_fold_apply): batches and
            # entries applied, time inside node.apply() (wall, and the
            # apply workers' own processor time), from hand-off
            # to apply and inside the user state machine's update, what
            # on-disk state machines appended to their own logs; and the
            # members' leader changes after each group's first leader
            apply_batches=0, apply_entries=0, t_apply_ms=0.0,
            t_apply_cpu_ms=0.0, t_apply_wait_ms=0.0, t_sm_update_ms=0.0,
            sm_wal_appends=0, sm_wal_bytes=0, leader_changes=0,
            # and what the members' replicas count on their NodeHost
            # (request.HOST_TOTALS): leader transfers asked for, ended
            # with the target leading (and the time that took) or
            # otherwise, proposals told DROPPED because another leader's
            # entries replaced theirs (node.py _settle_doomed)
            leader_transfers_requested=0, leader_transfers_done=0,
            leader_transfers_aborted=0, t_transfer_ms=0.0,
            proposals_dropped_truncated=0,
            # and what snapshots cost them (node.py "snapshotting"):
            # saves asked for and how each ended, from the request to
            # the save's start on a snapshot worker and inside the save
            # (compaction included; wall, and the snapshot workers' own
            # processor time), the containers' bytes, log entries
            # compacted away, InstallSnapshot messages sent, snapshots
            # recovered from
            snapshots_requested=0, snapshots_saved=0, snapshots_skipped=0,
            snapshot_failures=0, t_snapshot_wait_ms=0.0,
            t_snapshot_save_ms=0.0, t_snapshot_save_cpu_ms=0.0,
            snapshot_bytes=0, log_entries_compacted=0, snapshots_streamed=0,
            snapshots_recovered=0,
            # and what their ReadIndex reads cost (request.PendingReadIndex,
            # node.py "forwarded reads"): contexts and the reads they
            # carried, forwarded contexts stepped on the device at both
            # ends or on the host path at either end, reads forwarded,
            # reads whose index became known and reads answered, from
            # queued to index known and from index known to applied,
            # reads the forwarded-read ledger dropped
            read_ctxs=0, read_ctx_reads=0, fwd_read_ctxs_device=0,
            fwd_read_ctxs_host=0, fwd_reads=0, reads_confirmed=0,
            reads_completed=0, t_read_confirm_ms=0.0,
            t_read_apply_wait_ms=0.0, read_ledger_drops=0,
            # pipeline observability: host work overlapped with an
            # in-flight readback request (the double-buffering win) and
            # fences (drains to depth 0 forced by membership mutation)
            pipeline_overlap_s=0.0, pipeline_fences=0,
            # launches that raised after later generations chained on
            # them (_reset_after_pipeline_failure): the cluster
            # survives the rollback, the counter keeps it visible
            pipeline_resets=0,
            # fused commit waves (ISSUE 15): waves dispatched, rounds
            # stepped inside them, single-round fences (a routable-work
            # generation that could NOT fuse), and readback windows —
            # ONE per completed generation regardless of its round
            # count (plus one per exact-gather fallback round), the
            # counter proving one readback per fused wave
            fused_waves=0, fused_rounds_stepped=0, fused_fences=0,
            readback_windows=0,
        )
        # every collection a raft-gc region, those inside step calls
        # counted (_GcClock); close() unhooks it, and so does the core's
        # collection if nothing called close()
        self._gc = _GcClock(self)
        weakref.finalize(self, _gc_unhook, self._gc)
        self._arm_gc()

    def _compute_base(self, r) -> int:
        # the SHARD's shared base, not a per-row quantity — see __init__
        return self._shard_base.get(r.shard_id, 0)

    # -- the launch clock ------------------------------------------------
    def _phase(self, key: str) -> str:
        """Charge the time since the last switch to the phase that was
        running, and run ``key`` from now.  Returns the phase that was
        running, for the caller to switch back to.  Caller holds the
        core lock: the wall clock is the core's, not a thread's.  The
        processor clock is the calling thread's: leaving t_between_ms
        (the entry to a step call) starts its mark over and charges
        nothing; after that it is read only ``_CPU_READ_MIN_S`` apart."""
        now = _perf()
        prev = self._ph_key
        st = self.stats
        st[prev] += (now - self._ph_t) * 1000.0
        self._ph_key = key
        self._ph_t = now
        if prev == "t_between_ms":
            self._ph_cpu = _cpu()
            self._ph_cpu_t = now
        elif now - self._ph_cpu_t >= _CPU_READ_MIN_S:
            cpu = _cpu()
            st[_CPU_OF[prev]] += (cpu - self._ph_cpu) * 1000.0
            self._ph_cpu = cpu
            self._ph_cpu_t = now
        return prev

    def _enter(self, key: str, region: str) -> Tuple:
        """Open a phase: the clock, and the same extent as a named
        region on the profiler's clock.  Plain calls, not a ``with``:
        the phases are long stretches of the launch path, and a phase
        left open by an exception is closed by the next step call."""
        ann = _TraceAnnotation(region)
        ann.__enter__()
        return self._phase(key), ann

    def _leave(self, tok: Tuple) -> None:
        self._phase(tok[0])
        tok[1].__exit__(None, None, None)

    def _fold_apply(self) -> None:
        """Add what the members' apply workers did since the last call,
        and take the collection callback's totals.  They are other
        threads and count on their own ExecEngine / ``_GcClock``; the
        launch thread reads the totals here, under the core lock, so
        ``stats`` keeps one writer and lags by one step call at most."""
        st = self.stats
        for member in self._members:
            tot = member.totals()
            seen = member.folded
            if tot != seen:
                for name, now in tot.items():
                    key, scale = _MEMBER_TOTALS[name]
                    st[key] += (now - seen.get(name, 0)) * scale
                member.folded = tot
        st["launch_collections"], st["t_launch_gc_ms"] = self._gc.total

    def _arm_gc(self) -> None:
        """Hook the core's ``_GcClock`` into ``gc.callbacks`` (once)."""
        if self._gc not in gc.callbacks:
            gc.callbacks.append(self._gc)

    def close(self) -> None:
        """Unhook the collection callback; the last member's ``stop``
        calls it, and a member that joins later hooks it again."""
        _gc_unhook(self._gc)

    # -- persist: one phase over every call, the WAL's share inside -----
    def _persisting(self, persist, work, worker_id: int) -> None:
        if not work:
            return
        tok = self._enter("t_persist_ms", "raft-colocated-persist")
        try:
            persist(work, worker_id)
        finally:
            self._leave(tok)

    def _persist_and_process(self, updates, worker_id: int) -> None:
        self._persisting(super()._persist_and_process, updates, worker_id)

    def _persist_lane_batches(self, batches, worker_id: int) -> None:
        self._persisting(super()._persist_lane_batches, batches, worker_id)

    def _persist_lane_rows(self, rows, worker_id: int) -> None:
        self._persisting(super()._persist_lane_rows, rows, worker_id)

    def _db_save(self, db, save, *args) -> None:
        before = db.wal_counts()
        t0 = _perf()
        try:
            save(*args)
        finally:
            st = self.stats
            st["t_wal_ms"] += (_perf() - t0) * 1000.0
            after = db.wal_counts()
            st["wal_appends"] += after[0] - before[0]
            st["wal_bytes"] += after[1] - before[1]
            st["wal_records"] += after[2] - before[2]

    def _make_lease(self):
        """The lease as age lanes, renewed every launch, where the base
        engine keeps the window model (hostplane.LeaseAges)."""
        return hostplane.LeaseAges(
            self.capacity, self.P, node_of=lambda g: self._meta[g].node
        )

    def _arm_lease(self, g: int, r) -> None:
        self._lease.arm(g, r.election_timeout)

    def _lease_pass(  # hostplane-hot
        self, nodes, gs, clock, fed, n_step, skip, flags, vals_np, pos_sum,
        quiet,
    ) -> int:
        """Per-generation device-lease evidence pass: see
        hostplane.LeaseAges.  Runs before the bulk mirror write (role
        transitions read the OLD mirror) and before tick bookkeeping
        (the anchor is the clock BEFORE this launch's ticks, so the
        pass takes the ticks the bookkeeping is about to add).

        An array pass over the completion's rows: ``gs`` holds the
        ``n_step`` stepped rows, then the other live ones; ``clock`` and
        ``fed`` the clock ticks and the device ticks of the stepped
        rows, ``nodes`` the rows' nodes and ``skip`` the rows to leave
        alone (escalated, stopped, detached; None = none), ``quiet``
        the stepped rows whose host slots carried no message.  Python
        touches a row only where its role changed (arm / disarm);
        returns how many rows that was.  ``hostplane.lease_pass_rows``
        is the per-row twin the parity oracle runs beside it."""
        lease = self._lease
        gs_step = gs[:n_step]
        if skip is not None:
            keep = ~skip
            nodes = list(itertools.compress(nodes, keep.tolist()))
            gs = gs[keep]
            keep = keep[:n_step]
            gs_step, clock, fed = gs_step[keep], clock[keep], fed[keep]
            quiet = quiet[keep]
        walked = 0
        if vals_np is not None and len(vals_np):
            k = pos_sum[gs]
            has = np.nonzero(k >= 0)[0]
            g_has = gs[has]
            roles = vals_np[k[has], _R_ROLE]
            chg = np.nonzero(roles != self._mirror[_R_ROLE, g_has])[0]
            walked = len(chg)
            # raftlint: ignore[host-loop] residue: the rows whose role changed this launch
            for c in chg.tolist():
                r = nodes[has[c]].peer.raft
                if int(roles[c]) == _ROLE_LEADER_I and r.check_quorum:
                    lease.arm(int(g_has[c]), r.election_timeout)
                else:
                    lease.disarm(int(g_has[c]))
        armed, fresh, anchored = lease.lanes_step(
            gs_step, clock, fed, flags, quiet)
        self.stats["lease_rows_armed"] += armed
        self.stats["lease_rows_fresh"] += fresh
        self.stats["lease_rows_anchored"] += anchored
        return walked

    def device_coordinate(self, shard_id: int, replica_id=None):
        if self._mesh is None:
            return None
        if replica_id is None:
            gs = [
                g for (s, _r), g in self._row_of.items() if s == shard_id
            ]
            g = min(gs) if gs else None
        else:
            g = self._row_of.get((shard_id, replica_id))
        if g is None:
            return None
        return g // (self.capacity // self._mesh.size)

    def _pick_row(self, node) -> int:
        """Mesh-mode shard affinity: place a shard's replicas on the
        device block already hosting the shard, so a shard's commit
        rounds route intra-device and only cross-SHARD load spreads
        over the mesh (docs/MULTICHIP.md "Placement").  The scan is
        bounded to the free-list tail — with the striped base order the
        tail alternates blocks, so the preferred block is almost always
        within a few slots; after heavy churn it degrades gracefully to
        the plain pop."""
        if self._mesh is None:
            return self._free.pop()
        per = self.capacity // self._mesh.size
        want = None
        for (s, _r), g0 in self._row_of.items():
            if s == node.shard_id:
                want = g0 // per
                break
        if want is None:
            return self._free.pop()
        lo = max(0, len(self._free) - 4 * self._mesh.size)
        for i in range(len(self._free) - 1, lo - 1, -1):
            if self._free[i] // per == want:
                return self._free.pop(i)
        return self._free.pop()

    def _tier_caps(self, t: int) -> Dict[str, int]:
        return {k: min(self.capacity, v) for k, v in _SEL_TIERS[t].items()}

    # -- row identity ---------------------------------------------------
    def _row_key(self, node):
        # several NodeHosts share this engine: replicas of one shard are
        # distinct rows
        return (node.shard_id, node.replica_id)

    def _free_slot(self, g: int) -> None:
        """Return a row slot to the free pool — quarantined in
        ``_free_pending`` while generations are in flight (an in-flight
        merge still references the slot by id; re-attaching it before
        depth 0 would merge one replica's device effects into
        another's scalar state).  Flushed back at every drain."""
        (self._free_pending if self._inflight else self._free).append(g)

    def _flush_free_pending(self) -> None:
        if self._free_pending and not self._inflight:
            self._free.extend(self._free_pending)
            self._free_pending.clear()

    def _attach(self, node) -> Optional[int]:
        key = self._row_key(node)
        g = self._row_of.get(key)
        if g is not None and self._meta[g].node is not node:
            # replica restarted without a detach (stop raced the step):
            # drop the stale binding and re-key freshly.  PIPELINE
            # FENCE first — this is a membership mutation like any
            # detach, and in-flight merges still reference row g (the
            # old node's device acks must persist before the row is
            # released); the call site is the plan loop, never a
            # merge, so fencing is legal here (review finding)
            self._fence()
            self._row_of.pop(key)
            self._meta.pop(g, None)
            self._free_slot(g)
            self._release_row(g, node.shard_id)
            g = None
        is_new = key not in self._row_of
        g = super()._attach(node)
        if g is not None and is_new:
            self._host_shard[g] = node.shard_id
            self._host_replica[g] = node.replica_id
            self._host_peers[g, :] = 0
            self._tables_dirty = True
            self._wake_slot[g] = self._wake_slot_of(node)
            node.input_hint = self._input_hint.append
        return g

    def _wake_slot_of(self, node) -> int:
        ready = getattr(node, "step_work_ready", None)
        if ready is None:
            return -1
        for i, known in enumerate(self._wake_ready):
            if known is ready:
                return i
        for i in range(len(self._wake_ready)):
            if not (self._wake_slot == i).any():
                # a member that left with all its rows: take its place
                self._wake_ready[i] = ready
                return i
        self._wake_ready.append(ready)
        return len(self._wake_ready) - 1

    def _release_row(self, g: int, shard_id: int) -> None:
        """Clear the route-table claim of a freed row (caller holds the
        lock and has already popped _row_of/_meta).  Also drops the
        shard's entry cache when its last resident replica is gone —
        without this a process cycling many shards leaks one payload
        cache per shard id ever hosted."""
        self._host_shard[g] = 0
        self._host_replica[g] = 0
        self._host_peers[g, :] = 0
        self._wake_slot[g] = -1
        # before the slot can be attached again: a probe that loaded
        # the row's age must find its token moved (hostplane.LeaseAges)
        self._lease.disarm(g)
        self._lanes.reset_row(g, attached=False)
        self._tables_dirty = True
        if not any(
            s == shard_id for s, _ in self._row_of
        ):
            self._entry_cache.pop(shard_id, None)
            # base resets with the last replica; a returning shard with
            # a large log re-establishes it via _maybe_rebase_shards
            # before any row can pass the planner's lane bounds
            self._shard_base.pop(shard_id, None)
            self._rebase_block.pop(shard_id, None)

    def _halt_replica(self, g: int) -> None:
        node = self._meta[g].node
        super()._halt_replica(g)  # appends g to _free
        if self._inflight and g in self._free:
            # fail-stops happen mid-merge with later generations in
            # flight: quarantine the slot until depth 0 (see _free_slot)
            self._free.remove(g)
            self._free_pending.append(g)
        self._release_row(g, node.shard_id)

    def detach_replica(self, shard_id: int, replica_id: int) -> None:
        self.detach_replicas([(shard_id, replica_id)])

    def detach_replicas(self, pairs) -> None:
        """Batch detach under ONE core-lock acquisition (NodeHost.close
        releases every row of a member at once; per-row locking would
        interleave thousands of acquisitions with live launches).

        PIPELINE FENCE: membership must not mutate under an in-flight
        generation — the pending merges still reference these rows, and
        a stopping node's device acks were already routed, so its
        appends must persist before the row goes away (the ≤1-launch
        detach-race argument, now enforced at any depth by draining
        first: the drained merges run while the node is still live,
        then the row is released)."""
        with self._lock:
            self._fence()
            for shard_id, replica_id in pairs:
                g = self._row_of.pop((shard_id, replica_id), None)
                if g is not None:
                    self._meta.pop(g, None)
                    self._free_slot(g)
                    self._release_row(g, shard_id)

    def _upload_rows(self, rows) -> None:
        super()._upload_rows(rows)
        for g, r in rows:
            lay = np.zeros((self.P,), np.int64)
            for s, (pid, _) in enumerate(S.peer_layout(r)):
                lay[s] = pid
            if (self._host_peers[g] != lay).any():
                self._host_peers[g] = lay
                self._tables_dirty = True
            self._publish_ring_window(r)

    def _publish_ring_window(self, r) -> None:
        """Publish an uploading row's ring window to the shard cache:
        entries appended on the HOST path (scalar excursions, WAL
        replay) can later be device-route-replicated straight from this
        row's ring, and the receiving replica reconstructs payloads
        from the cache.  Witness rows must NOT publish — their own log
        holds stripped metadata entries (no cmd) under the same
        (index, term) keys; publishing them would overwrite real
        payloads in the shared cache and silently diverge any replica
        that reconstructs from it (witness RECEIVERS get the stripped
        form applied at _cache_lookup instead)."""
        if r.replica_id in r.witnesses:
            return
        last = r.log.last_index()
        lo = max(r.log.first_index(), last - self.W + 1)
        if last >= lo:
            try:
                ents = r.log._get_entries(lo, last + 1, 2**62)
            except Exception:  # noqa: BLE001 — compacted tails are fine
                ents = []
            self._cache_put(r.shard_id, ents)

    def _demote_row_to_host(self, node) -> None:
        g = self._row_of.get(self._row_key(node))
        if g is None:
            return
        meta = self._meta.get(g)
        if meta is None or meta.dirty:
            return
        self._evict_rows_to_host([g], "demote")  # drains pending routed traffic
        meta.set_escalation_hold(node.config)

    def _on_save_failure(self, pairs) -> None:
        super()._on_save_failure(pairs)
        # evict the failing nodes' rows (we hold the core lock:
        # colocated persist runs inside _step_colocated) so no further
        # device launch routes acks for appends their WAL cannot hold;
        # the scalar path only sends after a successful save.  With the
        # pipeline live this defers to the next depth-0 point (before
        # the next dispatch): the base class's save quarantine already
        # keeps the rows out of every new plan, and the ≤depth launches
        # already in flight were dispatched before the failure was
        # knowable — the same exposure window as the detach race.
        self._evict_rows_to_host([
            g
            for node, _u in pairs
            if (g := self._row_of.get(self._row_key(node))) is not None
        ], "save_failure")

    def _rebuild_tables(self) -> None:
        dest, rank = build_route_tables(
            self._host_shard, self._host_replica, self._host_peers
        )
        # the lease counts every resident peer's clock, cut off or not
        self._lease.set_peers(dest)
        if self._part_fn is not None:
            # cut cross-partition links by severing the device route:
            # the message is left undelivered (dest<0, counted in
            # routed_dropped) and the sending host re-sends it via its
            # transport, where the partition's drop hook loses it — the
            # destination row still ticks, campaigns and answers its
            # own side, which is what a real network partition does
            part = np.array([
                self._part_fn(int(s), int(r)) if s else 0
                for s, r in zip(self._host_shard, self._host_replica)
            ])
            cut = (dest >= 0) & (
                part[np.clip(dest, 0, len(part) - 1)] != part[:, None]
            )
            dest = np.where(cut, -1, dest)
        self._dest_dev = self._put_rows(dest)
        self._rank_dev = self._put_rows(rank)
        self._tables_dirty = False

    def set_partition(self, fn) -> None:
        """Install (or clear, with ``None``) a partition-group function
        ``fn(shard_id, replica_id) -> int``: device routes between rows
        in different groups are severed until cleared — cross-group
        messages fall back to each sender's host transport (chaos
        testing — see _rebuild_tables).  Takes effect from the next
        launch."""
        with self._lock:
            self._part_fn = fn
            self._tables_dirty = True

    # -- entry cache ----------------------------------------------------
    def _cache_put(self, shard_id: int, entries: List[Entry]) -> None:
        od = self._entry_cache.setdefault(shard_id, OrderedDict())
        for e in entries:
            od[(e.index, e.term)] = e
            od.move_to_end((e.index, e.term))
        while len(od) > self._cache_depth:
            # evict the LOWEST index, not the FIFO-oldest: a follower
            # catch-up re-inserts evicted low keys one batch at a time,
            # and FIFO eviction then rolls a wave through the insert
            # order that eventually eats the NEWEST entries — the very
            # ones the leader's ring can still device-route, fail-
            # stopping the follower at the last ring-window hop (r4
            # chaos finding: wedged at last-W+2 after a 300-entry lag)
            od.pop(min(od))

    def _cache_lookup(self, r, idx: int, term: int) -> Optional[Entry]:
        od = self._entry_cache.get(r.shard_id)
        e = od.get((idx, term)) if od else None
        if e is not None and r.replica_id in r.witnesses:
            e = r._to_witness_entry(e)
        return e

    # -- warm -----------------------------------------------------------
    def _warm(self) -> None:
        G, P, B, E, O = self.capacity, self.P, self.budget, self.E, self.O
        M, R = self.M, S.inbox_row_ints(self.M, self.E)
        self._pending = self._put_rows(make_inbox_packed(G, P * B, E))
        st = self._state
        # warm the REAL launch signature: the combo and the host inbox
        # both come out of _host_inbox over the launch's one committed
        # upload — warming with host-side arrays would key different
        # executables (committed-ness / sharding) and the first
        # production launch would recompile
        combo, host = _host_inbox(
            self._put(np.zeros((5 * G,), np.int32)), G=G, M=M, E=E, NSB=0
        )
        # the resident all-zero host inbox: rounds >= 2 of a fused wave
        # all read it — ticks and host slots are fed exactly once, in
        # round 1 (never donated, so one handle serves every wave)
        self._zero_host = host
        dest = self._put_rows(jnp.full((G, P), -1, I32))
        rank = self._put_rows(jnp.zeros((G, P), I32))
        # warm the PRODUCTION fused executable; it donates _pending, so
        # rebuild it afterwards
        new_st, out = _assemble_and_step(
            st, host, self._pending, combo, out_capacity=O
        )
        self._pending = self._put_rows(make_inbox_packed(G, P * B, E))
        merged_w, _regions_w, stats_w, packed_w, flags_w = _route_step(
            st, new_st, out, dest, rank, combo, PB=P * B, E=E, budget=B
        )
        # warm EVERY ladder tier: a tier change mid-run must find its
        # executable compiled — a mid-run compile freezes the launch
        # pipeline for seconds (chip_smoke.py requires zero retraces)
        for t in range(len(_SEL_TIERS)):
            caps = self._tier_caps(t)
            _select_and_blob(
                merged_w, out, stats_w, packed_w, flags_w, combo,
                CAP_B=caps["b"], CAP_SL=caps["sl"], CAP_N=caps["n"],
                CAP_A=caps["a"], CAP_S=caps["s"], HOST_OFF=P * B,
            )
        from .engine import _gather_rows, _scatter_rows, _select_rows

        _select_rows(self._put(jnp.ones((G,), bool)), st, st)
        pos0 = self._put_rows(jnp.full((G,), -1, jnp.int32))
        mask0 = self._put_rows(jnp.zeros((G,), bool))
        _zero_inbox_rows(self._pending, mask0)
        b = 1
        while b <= G:
            idx = self._put(jnp.zeros((b,), jnp.int32))
            sub = _gather_rows(st, idx)
            _scatter_rows(st, pos0, sub)
            _gather_detail(st, out, self._put(jnp.zeros((4, b), jnp.int32)))
            _gather_vals(st, out, idx)
            # the eviction drain gathers rows of the PENDING INBOX
            # (_drain_pending_to_host) — a distinct _gather_rows
            # signature the state-gather warms above don't cover; the
            # first post-warm eviction paid a fresh compile mid-run
            # (found by the analysis/jitcheck recompile sentry)
            _gather_rows(self._pending, idx)
            # the launch's one program at every bucket of dense rows
            combo_sc, host_sc = _host_inbox(
                self._put(np.zeros((5 * G + b * R,), np.int32)),
                G=G, M=M, E=E, NSB=b,
            )
            b <<= 1
        # under a mesh a program's outputs take the shardings the
        # compiler chose for THAT program, so the step over a launch
        # that carried dense rows may be a second signature: unwarmed,
        # it was a 17 s compile inside the first write (chip_smoke.py
        # --chips 4, PR 21); on one device this call hits the
        # executable above.
        _assemble_and_step(st, host_sc, self._pending, combo_sc,
                           out_capacity=O)
        self._pending = self._put_rows(make_inbox_packed(G, P * B, E))
        one = self._put(jnp.zeros((1,), jnp.int32))
        _set_remote_snapshot(st, one, one, one)
        jax.block_until_ready(self._state)
        if jitcheck.ENABLED:
            # recompile sentry baseline (analysis/jitcheck): the warm
            # set above is the COMPLETE post-warm compile surface
            jitcheck.mark_warm()

    def _evict_rows_to_host(self, gs, cause: str = "other") -> None:
        """Move resident rows to the host path losing nothing.  Order is
        a correctness invariant encoded ONCE here: drain each row's
        routed-but-unconsumed inbox traffic into its node's receive
        queue FIRST (the next launch's alive mask would destroy it —
        losing a heartbeat stream turns a brief host excursion into an
        election storm), then materialize device state into the scalar
        mirrors, then mark the rows host-authoritative.  Already-dirty
        rows are skipped wholesale: their scalar side is authoritative
        and materializing stale device lanes over it would corrupt it.
        Caller holds the core lock.

        PIPELINE FENCE: eviction mutates membership (rows leave the
        device), so in-flight generations drain to depth 0 first —
        their merges still reference these rows, and materializing a
        row whose unmerged device appends are in flight would trip a
        false divergence halt.  A caller running INSIDE a generation's
        merge (demote on a compacted below-ring send, a save-failure
        mid-persist) must not fence — completing later generations
        before the current one finishes would break the FIFO scalar
        sync — so the eviction defers to the next depth-0 point
        instead (before the next dispatch, see _run_deferred)."""
        if self._completing:
            self._deferred.append(("evict", [int(g) for g in gs], cause))
            return
        if self._inflight and any(
            (m := self._meta.get(g)) is not None and not m.dirty
            for g in gs
        ):
            self._fence()
        pairs = []
        for g in gs:
            meta = self._meta.get(g)
            if meta is not None and not meta.dirty:
                pairs.append((meta.node, g))
        if not pairs:
            return
        self.stats[f"evict_{cause}"] = (
            self.stats.get(f"evict_{cause}", 0) + len(pairs)
        )
        self._drain_pending_to_host(pairs)
        self._materialize_rows([g for _, g in pairs])
        for _, g in pairs:
            meta = self._meta.get(g)
            if meta is not None:
                meta.dirty = True

    def _drain_pending_to_host(self, pairs) -> None:
        """Decode rows' pending routed-inbox regions into wire Messages
        and enqueue them on the owning nodes (rows transitioning device
        -> host).  REPLICATE payloads reconstruct from the entry cache;
        an unreconstructible message is dropped (raft retries it)."""
        from ..pb import Message, MessageType
        from .engine import _gather_rows
        from .types import MT_REPLICATE

        if self._pending is None or not pairs:
            return
        idx = self._put(_pad_idx([g for _, g in pairs]))
        sub = unpack_inbox(jax.tree.map(
            np.asarray, self._run(_gather_rows, self._pending, idx)
        ))
        for k, (node, g) in enumerate(pairs):
            r = node.peer.raft
            base = int(self._base[g])  # routed lanes are shard-rebased
            for s in range(sub.mtype.shape[1]):
                mt = int(sub.mtype[k, s])
                if mt == 0:
                    continue
                n = int(sub.n_entries[k, s])
                msg = _shift_msg_indexes(
                    Message(
                        type=MessageType(mt),
                        to=node.replica_id,
                        from_=int(sub.from_id[k, s]),
                        shard_id=node.shard_id,
                        term=int(sub.term[k, s]),
                        log_term=int(sub.log_term[k, s]),
                        log_index=int(sub.log_index[k, s]),
                        commit=int(sub.commit[k, s]),
                        reject=bool(sub.reject[k, s]),
                        hint=int(sub.hint[k, s]),
                        hint_high=int(sub.hint_high[k, s]),
                    ),
                    base,
                )
                ents = []
                ok = True
                if mt == MT_REPLICATE and n > 0:
                    for j in range(n):
                        e = self._cache_lookup(
                            r,
                            msg.log_index + 1 + j,
                            int(sub.ent_term[k, s, j]),
                        )
                        if e is None:
                            ok = False
                            break
                        ents.append(e)
                if not ok:
                    continue
                if ents:
                    msg = dataclasses.replace(msg, entries=tuple(ents))
                node.enqueue_received(msg)
        # drained => CLEARED: the pending copies are dead the moment
        # they re-enter the host queues.  Without this, a shard rebase
        # that re-uploads its rows in the SAME step re-delivers the
        # stale copies with index lanes encoded against the OLD base
        # (review finding: healthy replicas fail-stopped on the shifted
        # replicates); the host-excursion path only survived it because
        # drained rows stayed dirty through the next launch's alive mask.
        mask = np.zeros((self.capacity,), bool)
        mask[[g for _, g in pairs]] = True
        self._pending = self._run(
            _zero_inbox_rows, self._pending, self._put_rows(mask)
        )

    # -- the launch pipeline -------------------------------------------
    def _fence(self) -> None:
        """Drain the pipeline to depth 0, run the deferred membership
        actions and persist every drained update — invoked before any
        membership mutation (evict/detach/rebase/stale re-attach).
        No-op when nothing is in flight or deferred.  Caller holds the
        core lock; must NOT be called from inside a generation's merge
        (those paths defer instead — see _evict_rows_to_host)."""
        if not self._inflight and not self._deferred:
            self._flush_free_pending()
            return
        if self._inflight:
            self.stats["pipeline_fences"] += 1
        self._drain_pipeline()

    def _persist_completed(self, updates: List[Tuple]) -> None:
        """Persist what one completion (or the deferred actions)
        emitted BEFORE the next generation completes.  A completion's
        early pass (_lane_commit_pass) persists its rows and hands
        their committed entries to apply at once; a classic update of
        the SAME node from the generation before, still waiting in a
        list, would then be saved and processed after it: an older hard
        state written over a newer one, and ``invalid processed`` out
        of ``log.commit_update`` (the cursor had moved past it).  So no
        update outlives the completion that emitted it."""
        if updates:
            self._drain_update_retries(updates)
            self._persist_and_process(updates, self._last_worker_id)

    def _drain_pipeline(self) -> None:
        """Complete every in-flight generation in dispatch order,
        persisting each one's updates as it completes, then run and
        persist the deferred actions."""
        while self._inflight:
            self._persist_completed(self._complete_oldest())
        self._persist_completed(self._run_deferred())
        self._flush_free_pending()

    def _complete_oldest(self) -> List[Tuple]:
        rec = self._inflight.popleft()
        self._completing = True
        try:
            return self._complete_generation(rec)
        except BaseException:
            # the generation chain is poisoned (its outputs feed every
            # later in-flight handle): roll the resident set back to
            # the last merged generation
            if not rec.clocked:
                self._clock_unmerged(rec.batch, rec.lane)
            self._reset_after_pipeline_failure()
            raise
        finally:
            self._completing = False

    def _run_deferred(self) -> List[Tuple]:
        """Execute deferred membership actions (escalation replays,
        snapshot-below/save-failure evictions, demotes) in the order
        they were recorded — only at depth 0, so every generation that
        stepped the affected rows has merged first.  Returns updates to
        persist.  Reentrancy guard: an action's own eviction fences,
        which calls back here — the inner call no-ops and the outer
        loop keeps draining."""
        if self._running_deferred or self._inflight:
            return []
        updates: List[Tuple] = []
        self._running_deferred = True
        try:
            while self._deferred and not self._inflight:
                action = self._deferred.pop(0)
                kind = action[0]
                if kind == "esc":
                    updates.extend(
                        self._apply_escalation(action[1], action[2],
                                               action[3])
                    )
                elif kind == "evict":
                    # covers mid-merge demotes and save-failure
                    # quarantine evictions too — both defer through
                    # _evict_rows_to_host's completing check
                    self._evict_rows_to_host(action[1], action[2])
                elif kind == "below":
                    self._apply_snapshot_below(action[1])
        finally:
            self._running_deferred = False
        return updates

    def _apply_escalation(self, node, g: int, si) -> List[Tuple]:
        """Deferred kernel-escalation recovery — the pipeline-safe form
        of the serial restore-and-replay.  The device already restored
        the row's pre-step state (_route_step's suppress mask), and any
        LATER in-flight generation re-stepped it from there: a valid
        raft evolution whose routed acks were delivered, so its effects
        merged normally before this runs (FIFO drain).  Recovery is
        therefore a plain eviction of the row's CURRENT device state
        (drains pending routed traffic, materializes, marks dirty)
        followed by a scalar replay of the escalated generation's
        drained inputs — late replay of messages/proposals/ticks is
        raft-safe, and at depth 1 the current state IS the restored
        pre-step state, so this degenerates to the old serial shape."""
        meta = self._meta.get(g)
        if meta is None or meta.node is not node or node.stopped:
            return []
        self._evict_rows_to_host([g], "escalation")
        meta = self._meta.get(g)
        if meta is None:  # halted during the eviction's materialize
            return []
        meta.set_escalation_hold(node.config)
        if si is None:
            return []  # routed-only inputs: raft-safe to lose
        u = node.step_with_inputs(si)
        return [(node, u)] if u is not None else []

    def _apply_snapshot_below(self, below) -> None:
        """Deferred snapshot-below host excursion: evict the rows (the
        int32 lane can't represent the durable snapshot index), then
        mark the scalar remotes SNAPSHOT — after the materialize, which
        would otherwise overwrite them and re-fire duplicate full
        snapshot streams on every re-upload."""
        gs = sorted({t[0] for t in below})
        for g in gs:
            self._count_snapshot_eviction(g)
        self._evict_rows_to_host(gs, "snapshot_below")
        for g, p, _, pid, ss_index in below:
            meta = self._meta.get(g)
            if meta is None or meta.node.stopped:
                continue
            rm = meta.node.peer.raft.get_remote(pid)
            if rm is not None:
                rm.become_snapshot(ss_index)

    def _floor_wait(self, t_req: float) -> None:
        """Simulated link latency (tests): data counts as landed no
        earlier than the floor after the D2H request was issued.  A
        request issued at dispatch and collected after host work pays
        only the remainder — the overlap the pipeline exists for."""
        if self._sync_floor_s <= 0:
            return
        rem = self._sync_floor_s - (_time.monotonic() - t_req)
        if rem > 0:
            _time.sleep(rem)

    def _collect_blob(self, dev, t_req: float) -> np.ndarray:
        """THE launch readback: blocking collect of a blob whose D2H
        copy was requested at dispatch, honoring the sync-floor shim."""
        # raftlint: ignore[sync-budget] the single sanctioned blob readback of the launch path
        arr = np.asarray(dev)
        self._floor_wait(t_req)
        return arr

    def _reset_after_pipeline_failure(self) -> None:
        """A launch program failed after later generations chained onto
        its outputs: every in-flight handle (state, pending regions,
        blobs) is transitively poisoned.  Roll the WHOLE resident set
        back to the last merged generation: scalar state is
        authoritative through it, and the unmerged generations' effects
        existed only device-side — appends and the acks they earned
        vanish TOGETHER for every colocated row (one shared device
        state), which is raft-safe message loss.  Rows re-upload from
        scratch on their next step."""
        # keep the one-readback identity (readback_windows + in-flight
        # == launches + sel_fallbacks, the fused-round smoke's gate) an
        # invariant across resets: the discarded generations' windows
        # will never be collected, so account them here
        self.stats["pipeline_resets"] += 1
        self.stats["readback_windows"] += len(self._inflight)
        # the discarded generations' ticks left their nodes' tick lanes
        # at the plan loop; their completions' bookkeeping will never
        # run, so both clocks advance here: no tick is lost to a reset
        for rec in self._inflight:
            if not rec.clocked:
                self._clock_unmerged(rec.batch, rec.lane)
        self._inflight.clear()
        self._pending_live = False
        self._flush_free_pending()
        for g, meta in list(self._meta.items()):
            if not meta.dirty:
                meta.dirty = True
                meta.plan_ok = False
                if meta.node.device_reads.has_pending():
                    meta.node.drop_device_reads()
                if meta.node.device_forwarded_reads:
                    meta.node.drop_forwarded_reads()
        try:
            self._state = self._put_rows(
                make_state_packed(self.capacity, self.P, self.W,
                                  replica_ids=np.zeros(self.capacity))
            )
            self._pending = self._put_rows(make_inbox_packed(
                self.capacity, self.P * self.budget, self.E
            ))
        except Exception:  # noqa: BLE001 — rebuilt lazily next launch
            self._pending = None

    # -- the colocated step --------------------------------------------
    def step_shards(self, nodes, worker_id: int) -> None:
        if all(n.stopped or n.stopping for n in nodes):
            # teardown fast path: don't contend for the core lock (the
            # owning worker may be asked to stop while we'd be queued
            # behind another member's multi-second launch)
            return
        # floor pre-wait: never hold the core lock just to wait out a
        # readback's latency floor.  Two shapes paid the floor IN the
        # lock and stalled every other worker's fresh proposal behind
        # ~a full floor (measured: the unloaded probe sat at ~2
        # floors): (a) the poke-driven idle drain (no node has work —
        # the call exists only to merge the tail generation) blocking
        # on the oldest collect, and (b) the dispatch room check with
        # the pipe FULL, blocking on the oldest collect before a new
        # generation may launch.  Both waits are for the SAME event —
        # the oldest in-flight readback reaching its floor — so sleep
        # it out here in small slices with the lock free: an idle call
        # aborts the moment any of its nodes gains real work (it can
        # then dispatch), a full-pipe call waits regardless (it needs
        # the room anyway).  Racy peeks of the in-flight deque are
        # benign — the in-lock paths re-check everything.
        if self._sync_floor_s > 0 and self._inflight:
            # bounded at ONE floor from entry: under multi-worker
            # contention the oldest in-flight keeps getting fresher
            # (another worker merges + redispatches), and an unbounded
            # re-wait could starve this worker's nodes — past the
            # bound it falls into the lock and blocks there exactly as
            # before (correctness never depended on the pre-wait)
            _cap = _time.monotonic() + self._sync_floor_s
            while _time.monotonic() < _cap:
                if not self._inflight:
                    break
                try:
                    t_req = self._inflight[0].t_req  # racy peek
                except IndexError:
                    break
                rem = t_req + self._sync_floor_s - _time.monotonic()
                if rem <= 0:
                    break
                if (
                    len(self._inflight) < self._pipeline_depth
                    and any(n.has_work() for n in nodes)
                ):
                    break
                _time.sleep(min(rem, 0.002))
        t_wait = _perf()
        waiting = _TraceAnnotation("raft-colocated-lockwait")
        waiting.__enter__()
        with self._lock:
            waiting.__exit__(None, None, None)
            self.stats["t_lock_wait_ms"] += (_perf() - t_wait) * 1000.0
            # closes t_between_ms; the processor mark starts on this thread
            self._phase("t_misc_ms")
            t_in, cpu_in = self._ph_t, self._ph_cpu
            try:
                self._step_colocated(nodes, worker_id)
            finally:
                self._phase("t_between_ms")
                st = self.stats
                st["t_launch_ms"] += (self._ph_t - t_in) * 1000.0
                st["cpu_launch_ms"] += (self._ph_cpu - cpu_in) * 1000.0
                self._fold_apply()

    def _coalesce(self, nodes) -> List:
        """Pull every other attached node with queued work into this
        launch: a full-width kernel step costs the same whether it
        carries one member NodeHost's inputs or all of them, so one
        launch serves the whole cluster's tick generation instead of
        one launch per member (at 10k shards x 5 members that is the
        difference between 1 and 5 multi-second launches per
        generation).  Safe under the core lock: ALL colocated node
        stepping happens inside it, so no other worker can be draining
        these queues concurrently."""
        # throttle: the scan is O(resident rows) of pure Python and ran
        # once per generation — ~1000 small preload generations during a
        # 50k-row mass start made it the single largest cost of the r5
        # scale run (294 s).  Skipping it is always SAFE: a node with
        # work was notified, so its own exec worker delivers it in
        # `nodes` on an upcoming generation; coalescing is a batching
        # optimization, not a delivery guarantee.
        now = _time.monotonic()
        # interval scales with the measured scan cost (>=10x) so the
        # scan can never consume more than ~10% of wall time: at 250k
        # resident rows one scan is 1-2 s of Python and a fixed 200 ms
        # interval let it dominate the 50k-shard election
        # Inside the 200 ms floor at most ONE launch goes without a
        # scan: a launch that carries its caller's nodes alone feeds
        # ticks to that member's rows alone, and when launches of 80 ms
        # went two and three between scans, a member that won the lock
        # twice fed its followers twice between two anchors of their
        # leaders, whose leases then ran out on the followers' clocks
        # (hostplane.LeaseAges; PERF.md section 6, PR 34:
        # lease_read_pct 99 -> 95 at P=5 on the chip)
        since = now - self._last_coalesce_scan
        if since < 10 * self._scan_cost or (
                since < _SCAN_FLOOR_S and not self._scan_skipped):
            self._scan_skipped = True
            return list(nodes)
        self._scan_skipped = False
        self._last_coalesce_scan = now
        seen = {id(n) for n in nodes}
        out = list(nodes)
        for meta in self._meta.values():
            n = meta.node
            if (
                id(n) not in seen
                and not n.stopped
                and not n.stopping
                and n.has_work()
            ):
                seen.add(id(n))
                out.append(n)
        self._scan_cost = _time.monotonic() - now
        coalesced = len(out) - len(nodes)
        if coalesced:
            self.stats["coalesced_rows"] += coalesced
        return out

    def _pull_hinted(self, nodes) -> int:
        """Append to ``nodes`` (the launch's list, which it owns) every
        resident node the input hint names that this launch does not
        carry yet, is not stopping and still holds queued non-tick
        input; returns where the pulled ones start.  The cost is the
        rows producers touched since the last launch, not the resident
        rows the scan walks, so every launch takes it: a queued
        proposal or read waits for the next launch, not for its own
        member's or the next scan's.  A pulled row is drained of its
        queued input alone (drain_step_inputs(ticks=False)): its ticks
        wait for its own member's launch or the scan, as before, so the
        tick feed and the lease anchors it drives are the scan's.

        While the step calls' smoothed period is half the scan's floor
        or more, the hint is taken and nothing pulled: pulled input
        lengthens the launch that carries it, and at such periods the
        launch the scan skips leaves the other members' rows unfed past
        the floor, where their leaders' leases run out (PERF.md section
        6, PR 41: 115 ms launches read lease_read_pct 92.9)."""
        hint = self._input_hint
        n0 = len(nodes)
        now = _time.monotonic()
        # an idle gap counts as one floor: it says nothing of how long
        # a loaded launch runs
        self._period_avg += (
            min(now - self._pull_at, _SCAN_FLOOR_S) - self._period_avg
        ) / 16
        self._pull_at = now
        if not hint:
            return n0
        if 2 * self._period_avg >= _SCAN_FLOOR_S:
            hint.clear()
            self.stats["input_hint_closed"] += 1
            return n0
        carried = {id(n) for n in nodes}
        row_of, meta_of = self._row_of, self._meta
        for _ in range(len(hint)):
            n = hint.popleft()
            if id(n) in carried:
                continue
            carried.add(id(n))
            meta = meta_of.get(row_of.get((n.shard_id, n.replica_id), -1))
            if (
                meta is not None
                and meta.node is n
                and not n.stopped
                and not n.stopping
                and n.has_queued_input()
            ):
                nodes.append(n)
        return n0

    def _note_input_wait(self, si) -> None:
        """Charge ``t_input_wait_ms`` with a full-path drain's wait: from
        the first input queued since the last drain (``si.input_t0``,
        which the drain took off its node) to this one."""
        t0 = si.input_t0
        if t0 and (si.received or si.proposals or si.read_indexes
                   or si.config_changes or si.transfers):
            self.stats["t_input_wait_ms"] += (_perf() - t0) * 1000.0
            self.stats["input_waits"] += 1

    def _maybe_rebase_shards(self, nodes) -> None:
        """Whole-shard group rebasing (the colocated 64-bit story).

        When any row's committed outruns its shard's shared base by
        ``rebase_chunk``, every RESIDENT row of that shard leaves the
        device together — in-flight routed traffic drains to the host
        queues first, so no rebased int32 lane survives the base change
        — and the shard's base advances to the largest W-multiple safe
        for ALL its rows (min across rows; leader rows bound it by
        their laggiest peer lane).  Rows re-upload with the new base on
        their next step.  Reference: uint64 log indexes throughout
        raftpb [U]; this keeps the colocated device path unbounded
        instead of aging shards off at 2^31 (r03 verdict #4)."""
        need = set()
        for node in nodes:
            if node.stopped or node.stopping:
                continue
            r = node.peer.raft
            shard = node.shard_id
            if (
                r.log.committed - self._shard_base.get(shard, 0)
                >= self._rebase_chunk
                and r.log.committed >= self._rebase_block.get(shard, 0)
            ):
                need.add(shard)
        if not need:
            return
        # the trigger uses committed (device-synced every step); the
        # CANDIDATE base needs fresh peer lanes, which only materialize
        # refreshes — so pull the shard's rows off the device first,
        # then decide.  If the candidate cannot advance (a lagging peer
        # lane or a freshly joined replica pins the min), the base must
        # neither regress nor be retried every step (review finding:
        # drain/materialize thrash): back off until committed grows by
        # another chunk.
        self._evict_rows_to_host(
            [g for (shard, _), g in self._row_of.items() if shard in need],
            "rebase",
        )
        for shard in need:
            rafts = [
                self._meta[g].node.peer.raft
                for (s, _), g in self._row_of.items()
                if s == shard and self._meta.get(g) is not None
            ]
            if not rafts:
                continue
            candidate = min(
                VectorStepEngine._compute_base(self, r) for r in rafts
            )
            if candidate > self._shard_base.get(shard, 0):
                self._shard_base[shard] = candidate
                self._rebase_block.pop(shard, None)
                self.stats["shard_rebases"] += 1
            else:
                # back off by a FRACTION of the chunk, not a whole one:
                # a full-chunk block scheduled the retry at ~2x chunk,
                # which under the default chunk (2^30) lands at/past the
                # int32 planner ceiling — a transiently lagging peer
                # then doomed the shard to a whole-shard scalar eviction
                # even though a valid rebase opened up long before.
                # chunk//8 keeps the thrash amortized (one materialize
                # per chunk//8 commit growth) while leaving ~8 retries
                # of headroom before the ceiling.
                self._rebase_block[shard] = (
                    max(r.log.committed for r in rafts)
                    + max(self.W, self._rebase_chunk // 8)
                )

    def _plan_device(self, node, si, mirror_leader: bool, g):
        # a replica rejoining a shard whose base already advanced past
        # its committed position cannot upload (its lanes would go
        # negative): scalar path until host-wire catch-up reaches the
        # base.  Rows known at rebase time can never be in this state —
        # the candidate min() is bounded by them.
        if node.peer.raft.log.committed < self._shard_base.get(
            node.shard_id, 0
        ):
            return None
        return super()._plan_device(node, si, mirror_leader, g)

    def _step_colocated(self, nodes, worker_id: int) -> None:
        self._last_worker_id = worker_id
        # ---- opportunistic completion: the earliest ripe sync -------
        # Merge any in-flight generation whose readback has LANDED
        # (floor elapsed, value ready) without blocking: proposals
        # complete from the earliest sync that proves their commit, not
        # from the pipe-full room check several generations later.
        # Runs before planning, so the plan also sees the freshest
        # merged scalars the link can provide.
        while self._inflight:
            rec = self._inflight[0]
            if self._sync_floor_s > 0:
                if _time.monotonic() - rec.t_req < self._sync_floor_s:
                    break
            # EVERY round's blobs must have landed: the merge may read
            # any round's detail payload too, and blocking the core
            # lock on a still-in-flight transfer is exactly the stall
            # this non-blocking pass exists to avoid (review finding)
            if not all(
                dev.is_ready()
                for dev in (*rec.head_dev, *rec.detail_dev)
            ):
                break
            self._persist_completed(self._complete_oldest())
        if self._deferred:
            # deferred membership actions (recorded mid-merge, e.g. a
            # save-failure eviction during the driver's persist or an
            # escalation a ripe completion just surfaced) run before
            # anything new dispatches
            self._fence()
        updates: List[Tuple] = []
        host_rows: List[Tuple] = []
        # the stepped rows, split by what the plan loop saw of each:
        # the tick lane (every row whose whole input is hint-free
        # ticks — carried as parallel columns from here to the end of
        # the completion, never as a tuple, a StepInputs, a plan list
        # or a Message: hostplane.TickLane) and ``batch``, the active
        # rows, the only ones the launch and its completion walk in
        # Python
        batch: List[Tuple] = []
        lane = hostplane.TickLane()
        lane_g = lane.gs.append
        lane_fed = lane.fed.append
        lane_ticks = lane.ticks.append
        lane_node = lane.nodes.append
        tok = self._enter("t_coalesce_ms", "raft-colocated-coalesce")
        nodes = self._coalesce(nodes)
        pulled_from = self._pull_hinted(nodes)
        self._maybe_rebase_shards(nodes)
        self._leave(tok)
        tok = self._enter("t_plan_ms", "raft-colocated-plan")
        n_fast = 0
        # ---- batched plan classifier --------------------------------
        # ONE vectorized pass over the SoA lanes (ops/hostplane.py)
        # decides static eligibility for the whole generation —
        # plan_ok/dirty/esc_hold as bool lanes instead of per-row
        # _RowMeta attribute probes.  Rows that pass still re-verify
        # the cheap per-launch dynamic conditions (empty queues, clean
        # binding, no snapshot/read state) inline; rows that fail take
        # the scalar _plan_device classifier below — the escalation/
        # slow-path oracle, exactly the contract the plan_ok fast tick
        # lane (57 µs -> 5 µs per row) proved.
        row_of = self._row_of
        gs_list = [
            row_of.get((n.shard_id, n.replica_id), -1) for n in nodes
        ]
        static_arr = hostplane.classify_static(
            self._lanes, np.asarray(gs_list, np.int64)
        )
        # completions so far: one that runs between here and the upload
        # (a fence) is the only thing that can dirty a row classified
        # clean just now
        merged_before = self.stats["readback_windows"]
        if hostplane.PARITY:
            hostplane.check_classify_parity(
                self._lanes, gs_list, static_arr
            )
        static_ok = static_arr.tolist()
        # rows of nodes seen stopping THIS generation: cleared from the
        # launch's alive mask (their detach may still be queued behind
        # the core lock)
        self._gen_stopping = []
        # what registered before this loop, the loop sees for itself
        self._read_ctx_new.clear()
        for i, node in enumerate(nodes):
            if node.stopped or node.stopping:
                if gs_list[i] >= 0:
                    self._gen_stopping.append(gs_list[i])
                continue
            # ---- fast tick lane -------------------------------------
            # A clean resident row whose ONLY input is the lock-free
            # tick lane skips the drain lock and the full classifier:
            # the static checks were proven by the last full plan
            # (the plan_ok lane, batch-checked above) and everything
            # that can change them either arrives through the queues
            # (checked empty right here, GIL-atomic truthiness) or
            # invalidates plan_ok at its source.  At 50k rows the full
            # per-row plan was ~57 us and t_plan was 152 s of a 269 s
            # election (10k-shard TPU run); the fast lane is ~5 us.
            g = gs_list[i]
            pulled = i >= pulled_from
            if pulled:
                if not node.has_queued_input():
                    # the hint's row whose input a drain took since: no
                    # ticks here, so nothing left to step
                    continue
                self.stats["input_rows_pulled"] += 1
            meta = self._meta.get(g) if static_ok[i] and not pulled else None
            if (
                meta is not None
                and meta.node is node  # not a stale pre-restart binding
                and node not in self._save_quarantine
                and not (
                    node._received
                    or node._proposals
                    or node._read_indexes
                    or node.pending_read_index.queued
                    or node._config_changes
                    or node._cc_to_apply
                    or node._leader_transfers
                )
            ):
                r = node.peer.raft
                if not (r.read_index.pending or r.read_index.queue):
                    # ONE shared definition of the tick drain/cap/defer
                    # arithmetic (node.drain_ticks_only) — see its
                    # locking contract: this worker holds the core lock
                    ticks, gc_t = node.drain_ticks_only(
                        r.election_timeout // 2
                    )
                    q = node.quiesce
                    if q.enabled and ticks:
                        busy = bool(self._behind[g])
                        no_leader = int(self._mirror[_R_LEADER, g]) == 0
                        was = q.quiesced
                        ticks_dev = q.tick_n(ticks, busy=busy,
                                             block=no_leader)
                        if q.quiesced and not was:
                            node.broadcast_quiesce_enter()
                    else:
                        ticks_dev = ticks
                    n_fast += 1
                    if ticks_dev:
                        if node.device_reads.queue:
                            # a pending device read rides the tick's
                            # hint lanes: a sparse row, not a lone tick
                            batch.append((
                                node, g,
                                StepInputs(ticks=ticks, gc_ticks=gc_t),
                                [("tick", ticks_dev)],
                            ))
                        else:
                            lane_g(g)
                            lane_fed(ticks_dev)
                            lane_ticks(ticks)
                            lane_node(node)
                            if gc_t:
                                lane.gc[g] = gc_t
                    else:
                        self._clock_idle(node, g, ticks + gc_t)
                    continue
            # ---- full path ------------------------------------------
            si = node.drain_step_inputs(ticks=not pulled)
            self._note_input_wait(si)
            self._take_read_resps(node, si)
            self._count_carried_reads(node, si)
            if self._static_host_only(node):
                host_rows.append((node, si))
                self._count_host_forwards(node, si)
                continue
            g = self._attach(node)
            if g is None:
                host_rows.append((node, si))
                self._count_host_forwards(node, si)
                continue
            mirror_leader = (
                not self._meta[g].dirty
                and self._mirror[_R_ROLE, g] == int(RaftRole.LEADER)
            )
            plan = self._plan_device(node, si, mirror_leader, g)
            if plan is None:
                host_rows.append((node, si))
                self._count_host_forwards(node, si)
                continue
            # every static eligibility check passed: arm the fast lane
            self._meta[g].plan_ok = True
            if not plan and not self._meta[g].dirty:
                self._clock_idle(node, g, si.ticks + si.gc_ticks)
                continue
            batch.append((node, g, si, plan))

        self._evict_rows_to_host([
            g
            for node, _si in host_rows
            if (g := self._row_of.get(self._row_key(node))) is not None
        ], "host_plan")

        # host path runs under the core lock in colocated mode: update
        # construction for OTHER hosts' rows happens inside launches, so
        # one lock must order both (the per-host parallelism the base
        # engine preserves is deliberately traded away here)
        for node, si in host_rows:
            if node.stopped:
                continue
            u = node.step_with_inputs(si)
            self.stats["host_rows_stepped"] += 1
            if u is not None:
                updates.append((node, u))

        if n_fast:
            self.stats["fast_lane_rows"] = self.stats.get(
                "fast_lane_rows", 0
            ) + n_fast
        self._leave(tok)
        launched = False
        if batch or lane.gs or self._pending_live:
            # (a lane row always has a plan: its one tick)
            if (
                self._pending_live
                or lane.gs
                or any(plan for _, _, _, plan in batch)
            ):
                tok = self._enter("t_upload_ms", "raft-colocated-upload")
                dirty_lane = self._lanes.dirty  # one load; np bool [G]
                rows = [
                    (g, node.peer.raft)
                    for node, g, si, plan in batch
                    if dirty_lane[g]
                ]
                if lane.gs and (
                    self.stats["readback_windows"] != merged_before
                ):
                    # a lane row is clean by classify_static; one that
                    # a fence's completion or its deferred actions
                    # dirtied since (an escalation's eviction, a halt)
                    # uploads again, as a batch row always did
                    # raftlint: ignore[sync-budget] host-built index array, not a device readback
                    stale = np.nonzero(
                        dirty_lane[np.asarray(lane.gs, np.int64)]
                    )[0]
                    for i in stale.tolist():
                        rows.append(
                            (lane.gs[i], lane.nodes[i].peer.raft)
                        )
                self._upload_rows(rows)
                self._leave(tok)
                self._launch_generation(batch, lane)
                launched = True
            else:
                # pure preload: nothing to step and no routed traffic in
                # flight — skip the launch AND the upload (mass start
                # streams thousands of such registrations; r5 profiling
                # showed the incremental small-batch preload uploads
                # alone cost ~7 ms/replica of the start loop).  Rows
                # stay dirty/host-authoritative and upload lazily in
                # the first generation that actually steps them.
                # Clock bookkeeping matches what the launch path's live
                # loop would have done for these rows: si.ticks still
                # counts quiesce-swallowed ticks, gc_ticks the dropped.
                for node, g, si, plan in batch:
                    _tick_bookkeeping(node, si.ticks + si.gc_ticks)

        # ---- pipeline completion ------------------------------------
        # Depth 1 completes its own generation in-call (the serial
        # loop).  At depth >= 2 a dispatched generation stays in flight
        # until the pipe is FULL at the next dispatch (the room check
        # inside _launch_generation): its readback — requested at
        # dispatch — then stayed in flight for a full pipeline's worth
        # of host work (plan/upload/dispatch of the following
        # generations), which is what turns the sync floor from a
        # per-generation cost into a hidden one.  An idle call (nothing
        # to launch) drains fully so no generation waits on work that
        # never comes, and a completion that recorded deferred
        # membership actions forces a full drain — they must run
        # before the next dispatch.
        if (not launched) or self._pipeline_depth == 1 or self._deferred:
            while self._inflight:
                # in emission order: this call's own updates first
                self._persist_completed(updates)
                updates = self._complete_oldest()
        if self._deferred and not self._inflight:
            updates.extend(self._run_deferred())
        self._flush_free_pending()

        self._drain_update_retries(updates)
        if updates:
            self._persist_and_process(updates, worker_id)
        if self._inflight:
            # completion guarantee: a dispatched generation must be
            # merged even if no member ever has work again — poke ONE
            # live node so some worker calls back in (that call,
            # finding nothing to launch, drains the pipeline).  One
            # notify suffices and per-generation fan-out to the whole
            # batch measurably serialized the 1-core bench.  A
            # pending-live-only launch has an EMPTY batch (review
            # finding), so fall back to any alive resident node.
            tok = self._enter("t_wake_ms", "raft-colocated-wake")
            poked = False
            for node in itertools.chain(
                (row[0] for row in batch), lane.nodes
            ):
                if not node.stopped and node.notify_work is not None:
                    node.notify_work()
                    poked = True
                    break
            if not poked:
                for g in np.nonzero(self._lanes.alive_mask())[0].tolist():
                    meta = self._meta.get(g)
                    if (
                        meta is not None
                        and not meta.node.stopped
                        and meta.node.notify_work is not None
                    ):
                        meta.node.notify_work()
                        break
            self._leave(tok)

    def _sel_cover(self, G, caps, counts, sel_rows, sets):  # hostplane-hot
        """Index-array coverage of the device's single-sync row
        selection: when every host-side merge set is contained in the
        device-selected sections (and the counts fit the warmed
        capacity tier), return the five row->gather-position maps plus
        the vals source rows; ``None`` sends the launch down the exact
        two-sync fallback.  Replaces the old per-row ``*_at`` dict
        builds and ``all(g in …)`` membership scans (O(rows) Python per
        launch — pinned array-at-once by raftlint's host-loop rule)."""
        n_buf, n_slot, n_need, n_append, n_sum = counts
        if not (
            n_buf <= caps["b"] and n_slot <= caps["sl"]
            and n_need <= caps["n"] and n_append <= caps["a"]
            and n_sum <= caps["s"]
        ):
            return None
        rows_buf, rows_slot, rows_need, rows_append, rows_sum = sel_rows
        pos_buf = hostplane.pos_of(G, rows_buf[:n_buf])
        pos_slot = hostplane.pos_of(G, rows_slot[:n_slot])
        pos_need = hostplane.pos_of(G, rows_need[:n_need])
        pos_ring = hostplane.pos_of(G, rows_append[:n_append])
        pos_sum = hostplane.pos_of(G, rows_sum[:n_sum])
        if not (
            hostplane.covered(pos_buf, sets.buf_rows)
            and hostplane.covered(pos_slot, sets.slot_rows)
            and hostplane.covered(pos_need, sets.need_rows)
            and hostplane.covered(pos_ring, sets.append_rows)
            and hostplane.covered(pos_sum, sets.sum_rows)
        ):
            return None
        return (pos_buf, pos_slot, pos_need, pos_ring, pos_sum,
                rows_sum[:n_sum])

    def _bookkeeping_pass(self, live) -> None:
        """Batched tick bookkeeping for one generation's live rows —
        hoisted out of the merge loops so every row pays it exactly
        once, BEFORE any effects merge (and AFTER _lease_pass: the
        lease anchor is the PRE-launch clock).  Zero-tick rows (a
        launch-rate above the wall-tick cadence makes them the
        majority) skip with two attribute loads; ticked rows advance
        both clocks and take the hint-gated single-lock pending-table
        sweep inside _tick_bookkeeping.  ``live`` rows carry a
        ``StepInputs`` only if they are ACTIVE batch rows: the tick
        lane's rows are clocked by :meth:`_bookkeeping_lane`."""
        meta_get = self._meta.get
        for node, g, si in live:
            if si is None:
                continue
            t = si.ticks + si.gc_ticks
            if t and not node.stopped and meta_get(g) is not None:
                # _tick_bookkeeping, inlined (clock lockstep +
                # hint-gated single-lock pending-table sweep)
                tc = node.tick_count + t
                node.tick_count = tc
                node.peer.raft.tick_count += t
                if tc >= node.pending_deadline_hint[0]:
                    gc_tables(
                        node.pending_tables,
                        node.pending_deadline_hint, tc,
                    )

    def _bookkeeping_lane(self, lane, skip) -> None:
        """:meth:`_bookkeeping_pass` for the tick lane: its node and
        clock-tick columns side by side, no tuple and no
        ``StepInputs`` a row (a lane row always ticked).  ``skip``
        marks the lane rows to leave alone (escalated, stopped,
        detached), None where there are none.  The clocks are node
        attributes, so this is still one short loop body a row."""
        ticks = lane.clock_np.tolist()
        rows = zip(lane.nodes, ticks)
        if skip is not None:
            rows = itertools.compress(rows, (~skip).tolist())
        for node, t in rows:
            tc = node.tick_count + t
            node.tick_count = tc
            node.peer.raft.tick_count += t
            if tc >= node.pending_deadline_hint[0]:
                gc_tables(
                    node.pending_tables, node.pending_deadline_hint, tc
                )

    def _clock_unmerged(self, batch, lane) -> None:
        """Advance both clocks for a generation whose completion will
        never run (a launch that raised, a pipeline reset): the ticks
        it drained are gone from the nodes' tick lanes, and a clock
        that lost them would deliver every pending deadline late."""
        for node, g, si, _plan in batch:
            if si is not None and not node.stopped:
                self._clock_idle(node, g, si.ticks + si.gc_ticks)
        if len(lane):
            self._bookkeeping_lane(
                lane, self._skip_mask(lane.nodes, lane.gs_np)
            )
            self._lease.idle(lane.gs_np, lane.clock_np)

    def _clock_idle(self, node, g: int, ticks: int) -> None:
        """Both clocks of a resident row advance with no completion to
        do it (ticks that quiesce swallowed, a launch that raised): its
        lease ages by the same ticks, as it would on the scalar path
        (``Raft.lease_remaining_ticks`` reads the raft clock)."""
        if ticks:
            _tick_bookkeeping(node, ticks)
            self._lease.idle(g, ticks)

    def _skip_mask(  # hostplane-hot
        self, nodes, gs, esc_seen=(), n_stepped: int = 0
    ) -> Optional[np.ndarray]:
        """[n] bool over a completion's rows: those it leaves alone, as
        the per-row passes did — detached since the launch, stopped,
        or (of the first ``n_stepped``, the stepped rows) escalated in
        any round of the wave, whose recovery is the deferred evict +
        replay.  None where no row is."""
        skip = ~self._lanes.attached[gs]
        if any(map(_STOPPED, nodes)):
            skip |= np.fromiter(map(_STOPPED, nodes), bool, len(nodes))
        if esc_seen:
            skip[:n_stepped] |= np.isin(gs[:n_stepped], list(esc_seen))
        return skip if skip.any() else None

    def _clock_and_lease(self, rec, live, flags, vals_np, pos_sum,
                         esc_seen, touched, whole=None) -> None:
        """One completion's lease pass, then its tick bookkeeping (in
        that order: the lease anchor is the PRE-launch clock), over
        the stepped rows as arrays plus the few other live rows.
        ``whole`` (the parity oracle's: the live list as every
        completion built it before PR 29, one tuple a stepped row) has
        the per-row passes worked out beside the array ones and the
        two compared (hostplane.check_completion_parity)."""
        batch, lane = rec.batch, rec.lane
        n_act, n_step = len(batch), len(rec.batch_gs)
        gs, fed = rec.batch_gs, rec.fed
        nodes = [row[0] for row in batch]
        nodes += lane.nodes
        # what each stepped row's bookkeeping is about to add
        clock = np.empty((n_step,), np.int64)
        clock[:n_act] = [
            si.ticks + si.gc_ticks for _n, _g, si, _plan in batch
        ]
        clock[n_act:] = lane.clock_np
        # a batch row whose host slots held no message (a lane row is
        # fed, so it does not matter there)
        quiet = np.zeros((n_step,), bool)
        quiet[:n_act] = [not si.received for _n, _g, si, _plan in batch]
        stepped = np.zeros((self.capacity,), bool)
        stepped[gs] = True
        others = [
            (node, g) for node, g, si in live
            if si is None and not stepped[g]
        ]
        if others:
            nodes += [node for node, _g in others]
            gs = np.concatenate([gs, [g for _n, g in others]])
        skip = self._skip_mask(nodes, gs, esc_seen, n_step)
        if whole is not None:
            want = self._completion_reference(
                rec, whole, clock, flags, vals_np, pos_sum, touched, quiet
            )
        self.stats["completion_rows_walked"] += self._lease_pass(
            nodes, gs, clock, fed, n_step, skip, flags, vals_np, pos_sum,
            quiet,
        )
        self._bookkeeping_pass(live)
        if len(lane):
            self._bookkeeping_lane(
                lane, None if skip is None else skip[n_act:n_step]
            )
        rec.clocked = True
        if self._xfer_watch:
            self._transfer_targets_pass(batch, rec.fed_of)
        if whole is not None:
            lease = self._lease
            hostplane.check_completion_parity(
                hostplane.CompletionTrace(
                    emitted=_emitting(live, pos_sum, touched),
                    rows=want.rows, et=lease.et, age=lease.age,
                    own=lease.own, since=lease.since, clk=lease.clk,
                    clocks={
                        g: (node.tick_count, node.peer.raft.tick_count)
                        for node, g, _si in whole
                    },
                ),
                want,
            )

    # raftlint: ignore[host-loop] parity oracle: the per-row passes worked out over the whole live list
    def _completion_reference(self, rec, whole, clock, flags, vals_np,
                              pos_sum, touched,
                              quiet) -> "hostplane.CompletionTrace":
        """What the per-row lease and bookkeeping passes make of
        ``whole``: the lease pass run on a COPY of the lease lanes
        (hostplane.lease_pass_rows) and the clocks each row ends on,
        computed and not written."""
        lease = self._lease.copy()
        meta_get = self._meta.get
        hostplane.lease_pass_rows(
            lease, whole,
            dict(zip(rec.batch_gs.tolist(),
                     zip(clock.tolist(), rec.fed.tolist()))),
            flags, vals_np, pos_sum, self._mirror[_R_ROLE],
            lambda node, g: node.stopped or meta_get(g) is None,
            set(rec.batch_gs[quiet].tolist()),
        )
        clocks = {}
        for node, g, si in whole:
            t = 0
            if (
                si is not None and not node.stopped
                and self._meta.get(g) is not None
            ):
                t = si.ticks + si.gc_ticks
            clocks[g] = (node.tick_count + t,
                         node.peer.raft.tick_count + t)
        rows = np.asarray([g for _n, g, _si in whole], np.int64)
        return hostplane.CompletionTrace(
            emitted=_emitting(whole, pos_sum, touched),
            rows=rows, et=lease.et, age=lease.age, own=lease.own,
            since=lease.since, clk=lease.clk, clocks=clocks,
        )

    def _wake_alive(self) -> None:  # hostplane-hot
        """Wake the step worker of every alive resident row: the same
        ready sets as one ``notify_work()`` a row, through one
        ``WorkReady.notify_all`` a member NodeHost (what its
        ``ExecEngine.notify_many`` is) — one lock round a partition,
        not one a row."""
        alive = self._lanes.alive_mask()
        slot = self._wake_slot
        # raftlint: ignore[host-loop] one pass a member NodeHost, not a row
        for i, ready in enumerate(self._wake_ready):
            ids = self._host_shard[alive & (slot == i)]
            if len(ids):
                ready.notify_all(ids.tolist())
        rest = alive & (slot < 0)
        if rest.any():
            # nodes no ExecEngine registered (direct-drive tests,
            # bespoke engines): the per-row call, where there is one
            # raftlint: ignore[host-loop] fallback for nodes without a step_work_ready
            for g in np.nonzero(rest)[0].tolist():
                meta = self._meta.get(g)
                if meta is not None and meta.node.notify_work is not None:
                    meta.node.notify_work()

    def _live_rows(self, rec, flags, sets, esc_seen,
                   esc_other: bool) -> List[Tuple]:
        """The rows of one round a completion walks in Python, as
        ``(node, g, si)``: the ACTIVE batch rows, the tick lane's rows
        the round's flags mark (``F_ANY_LIVE``: one mask over the
        lane's row ids — an unmarked lane row only ticked, and the
        array passes clock and lease it), and the resident rows routed
        traffic gave effects to (``sets.live_other``); less the rows
        escalated in any round so far (``esc_other``: the other rows
        too — the intermediate rounds' rule)."""
        if esc_seen:
            live = [
                (node, g, si) for node, g, si, _plan in rec.batch
                if g not in esc_seen
            ]
        else:
            live = [(node, g, si) for node, g, si, _plan in rec.batch]
        lane = rec.lane
        if len(lane):
            hit = np.nonzero((flags[lane.gs_np] & _F_ANY_LIVE) != 0)[0]
            gs, nodes = lane.gs, lane.nodes
            for i in hit.tolist():
                if gs[i] not in esc_seen:
                    live.append((nodes[i], gs[i], None))
        meta_get = self._meta.get
        for g in sets.live_other.tolist():
            if esc_other and g in esc_seen:
                continue
            meta = meta_get(g)
            if meta is not None:
                live.append((meta.node, g, None))
        return live

    # raftlint: ignore[host-loop] parity oracle: the whole-batch live list every completion built before PR 29
    def _live_rows_whole(self, rec, sets, esc_seen,
                         esc_other: bool) -> List[Tuple]:
        """Per-row twin of :meth:`_live_rows`: every stepped row as a
        tuple with its ``StepInputs``, lane rows included."""
        n = len(rec.batch_gs)
        live = [
            row for row in map(rec.stepped_row, range(n))
            if row[1] not in esc_seen
        ]
        for g in sets.live_other.tolist():
            if esc_other and g in esc_seen:
                continue
            meta = self._meta.get(g)
            if meta is not None:
                live.append((meta.node, g, None))
        return live

    def _lane_commit_pass(self, live, flags, pos_sum, pos_buf, pos_slot,
                          pos_need, vals_np, early_done) -> None:
        """Array-side update assembly for commit-only rows — the
        update-lane contract (ISSUE 13; docs/PARITY.md).

        Eligible: live rows with a values entry but no append, no
        host-visible outbox bytes, no proposal slots and no
        snapshot-needing peer — their whole merge is the scalar sync +
        commit advance + update emission, none of which touches the
        detail payload.  One ``plan_update_sync`` pass over the update
        lanes classifies their effects (``U_*`` bits vs the last
        synced words); the residual loop then only writes the scalar
        words that moved and collects ``(node, term, vote, commit,
        entries)`` LANE tuples for ONE batched ``_persist_lane_rows``
        call — no per-row ``get_update`` walk, no per-row Update/
        State/UpdateCommit objects.  On the pipelined path this still
        runs straight off the HEAD blob, so a proposal whose commit
        this generation proves completes without waiting for the
        detail payload (PR 11's early-completion win, kept).

        Rows with scalar-side residue (pending raft msgs / reads /
        drops / unsaved entries / snapshot — a resident-clean row
        should never accumulate any; defense in depth) fall back to
        the classic get_update emission.  Marks completed positions in
        ``early_done`` so the heavy loop skips them."""
        if not live:
            return
        # raftlint: ignore[sync-budget] host-built index array, not a device readback
        gs_all = np.asarray([g for _, g, _ in live], np.int64)
        sum_k = pos_sum[gs_all]
        eligible = (
            (sum_k >= 0)
            & ((flags[gs_all] & _F_APPEND) == 0)
            & (pos_buf[gs_all] < 0)
            & (pos_slot[gs_all] < 0)
            & (pos_need[gs_all] < 0)
        )
        if not eligible.any():
            return
        idx = np.nonzero(eligible)[0]
        gs = gs_all[idx]
        k_sel = sum_k[idx]
        old_w = self._ulanes.words[:, gs]
        uplan = hostplane.plan_update_sync(
            old_w, k_sel, vals_np, self._base[gs]
        )
        if hostplane.PARITY:
            hostplane.check_update_plan_parity(
                old_w, k_sel, vals_np, self._base[gs], uplan
            )
        # rows the loop below skips (stopped/halted mid-flight) are
        # freed and re-seeded at their next upload — bulk write is moot
        # for them, exactly the mirror-table argument
        self._ulanes.words[:, gs] = uplan.words
        ub_l = uplan.ubits.tolist()
        w_term = uplan.words[_R_TERM].tolist()
        w_vote = uplan.words[_R_VOTE].tolist()
        w_com = uplan.words[_R_COMMIT].tolist()
        w_lead = uplan.words[_R_LEADER].tolist()
        w_role = uplan.words[_R_ROLE].tolist()
        # rows eligible for the array-batched persist (hard-state
        # effect, slot-backed store; `eligible` already proved no heavy
        # sections) — the loop only records exceptions; commit rows
        # hand (node, entries) to the post-save apply leg
        so_mask = (
            ((uplan.ubits & (U_STATE | U_COMMIT)) != 0)
            & (self._lane_dbi[gs] >= 0)
        )
        so_drop: List[int] = []
        meta_get = self._meta.get
        lane_rows: List[Tuple] = []
        lane_append = lane_rows.append
        lane_apply: List[Tuple] = []
        fulls: List[Tuple] = []
        for j, ub, term, vote, committed, leader, role, so in zip(
            idx.tolist(), ub_l, w_term, w_vote, w_com, w_lead, w_role,
            so_mask.tolist(),
        ):
            node, g, si = live[j]
            early_done[j] = True
            if node.stopped or meta_get(g) is None:
                if so:
                    so_drop.append(j)
                continue
            r = node.peer.raft
            log = r.log
            im = log.inmem
            # NOTE: open-coded in lockstep with the engine lane branch
            # — see the note in engine._device_step
            if (
                r.msgs or r.ready_to_reads or r.dropped_entries
                or r.dropped_read_indexes or im.snapshot.index
                or im.saved_to + 1 - im.marker < len(im.entries)
            ):
                # residue: the classic path drains it
                if so:
                    so_drop.append(j)
                r.term, r.vote, r.leader_id = term, vote, leader
                r.role = _ROLE_OF[role]
                if node.device_forwarded_reads:
                    node.check_forwarded_reads(term, leader)
                if committed > log.committed:
                    log.commit_to(committed)
                if (
                    role != _ROLE_LEADER_I
                    and node.device_reads.has_pending()
                ):
                    node.drop_device_reads()
                u = node.peer.get_update(
                    last_applied=node.sm.last_applied
                )
                node.dispatch_dropped(u)
                fulls.append((node, u))
                node._check_leader_change()
                continue
            if ub & U_STATE:
                r.term = term
                r.vote = vote
            if ub & U_LEADER:
                r.leader_id = leader
            if ub & U_ROLE:
                r.role = _ROLE_OF[role]
            if node.device_forwarded_reads:
                node.check_forwarded_reads(term, leader)
            if ub & U_LOST_LEAD and node.device_reads.has_pending():
                # leadership lost: confirmations will never arrive.
                # Exact for lane rows — device reads only register off
                # merged outbox messages (a heavy row by definition),
                # so any pending read predates this sync and the
                # losing transition is THIS generation's lane diff
                # (docs/PARITY.md "Update-lane contract").
                node.drop_device_reads()
            if ub & U_COMMIT:
                log.commit_to(committed)
                ce = log.entries_to_apply()
                if so:
                    lane_apply.append((g, node, ce))
                else:
                    lane_append((node, term, vote, committed, ce))
            elif ub & U_STATE and not so:
                # hard-state move without a slot-backed store
                lane_append((node, term, vote, committed, None))
            if ub & U_LEADER:
                node._check_leader_change()
        if so_mask.any():
            if so_drop:
                so_mask &= ~np.isin(idx, np.asarray(so_drop))
            ii = np.nonzero(so_mask)[0]
            if len(ii):
                gs_so = gs[ii]
                dbi = self._lane_dbi[gs_so]
                slots = self._lane_slot[gs_so]
                w = uplan.words
                app_by_db: Dict[int, List] = {}
                if lane_apply:
                    dbi_all = self._lane_dbi
                    for g2, node, ce in lane_apply:
                        app_by_db.setdefault(
                            int(dbi_all[g2]), []
                        ).append((node, ce))
                batches = []
                for d in np.unique(dbi).tolist():
                    m = dbi == d
                    im_ = ii[m]
                    batches.append((
                        self._lane_dbs[d], slots[m], w[_R_TERM][im_],
                        w[_R_VOTE][im_], w[_R_COMMIT][im_], live,
                        idx[im_], app_by_db.get(d, ()),
                    ))
                self._persist_lane_batches(
                    batches, self._last_worker_id
                )
        if lane_rows:
            self._persist_lane_rows(lane_rows, self._last_worker_id)
        if fulls:
            self._persist_and_process(fulls, self._last_worker_id)

    def _attach_messages(self, r, node, *args, **kwargs) -> None:
        # the one place a device read REGISTERS (handle_device_read_resp
        # off a merged outbox row).  From then on the row's tick carries
        # the read's ctx in its hint lanes, so it is no tick-lane row:
        # remember the node for a launch whose plan loop classified it
        # BEFORE this merge ran (the room check and the eviction fence
        # complete generations between the plan loop and the encode).
        reads = node.device_reads
        had = bool(reads.queue)
        super()._attach_messages(r, node, *args, **kwargs)
        if not had and reads.queue:
            self._read_ctx_new.append(node)

    def _retake_lane_rows(self, batch, lane) -> None:
        """Move rows whose node registered a device read since the plan
        loop from the tick lane to the active rows (see
        _attach_messages), each with the ``StepInputs`` and the plan a
        batch row has: O(such rows), usually none."""
        for node in self._read_ctx_new:
            g = self._row_of.get(self._row_key(node))
            if g is None or not node.device_reads.queue or g not in lane.gs:
                continue
            if lane.nodes[lane.gs.index(g)] is node:  # no stale binding
                _node, fed, ticks, gc_t = lane.take(g)
                batch.append((
                    node, g, StepInputs(ticks=ticks, gc_ticks=gc_t),
                    [("tick", fed)],
                ))
        self._read_ctx_new.clear()

    def _encode_generation(self, active, tick_gs,
                           tick_n) -> hostplane.LaunchEncode:
        """The encode phase's host half.  ``active`` rows go through
        ``_encode_rows`` into ``Message`` lists, staging and proposal
        rows, and split into lone hint-free ticks (a count in the [G]
        vector) and dense inbox rows; the tick lane's rows go into the
        same vector as two arrays (hostplane.encode_tick_lane) and are
        never walked.  With an empty lane and the whole stepped set as
        ``active`` this is the pre-lane encode: the parity oracle."""
        # staging keys in ASSEMBLED coordinates: the routed regions
        # (width P*B) come first, host slots after (see _assemble_inbox)
        row_msgs_of, staging, prop_rows, fed = self._encode_rows(
            active, slot_offset=self.P * self.budget
        )
        # compact host-inbox upload: tick-only rows (the overwhelming
        # majority at scale) ride a [G] count vector built into an inbox
        # ON DEVICE; only rows with real host slots upload dense rows
        tick_counts = hostplane.encode_tick_lane(
            self.capacity, tick_gs, tick_n
        )
        sparse = hostplane.split_lone_ticks(tick_counts, active, row_msgs_of)
        return hostplane.LaunchEncode(
            tick_counts, sparse, staging, prop_rows, fed
        )

    @staticmethod
    def _lane_as_batch(lane) -> List[Tuple]:
        """The lane's rows as the batch tuples they were before PR 29:
        the parity oracle's input, and nothing else's."""
        return [
            (node, g, _lane_inputs(lane, i), [("tick", n)])
            for i, (node, g, n) in enumerate(
                zip(lane.nodes, lane.gs, lane.fed))
        ]

    def _launch_generation(self, batch, lane) -> None:  # sync-hot
        """Assemble, upload and dispatch one generation, request its
        (head, detail) readback, and push the in-flight record — the
        merge tail runs later in _complete_generation (behind the
        device by up to pipeline_depth generations).  ``batch`` (the
        active rows) and ``lane`` (the tick-only rows, as columns)
        split the stepped rows between them (see _step_colocated).
        Caller holds the core lock."""
        # room check: the pipe holds up to depth dispatched-unmerged
        # generations; complete the oldest BEFORE adding a new one so
        # each readback stays in flight across a full pipeline's worth
        # of host work — completing right after dispatch (the naive
        # order) gave every readback only ONE cycle of overlap and
        # left half the floor exposed on the 1-core bench.  (An
        # "express" +1 slot for proposal-carrying waves was tried and
        # REVERTED: exceeding the depth makes the next dispatch drain
        # TWO generations, the second still mid-floor — a systematic
        # in-lock stall that measured worse than the wait it removed.)
        while len(self._inflight) >= self._pipeline_depth:
            self._persist_completed(self._complete_oldest())
        # encode: the batch into inbox rows, the per-launch [G] inputs
        # and the host inbox onto the device — everything between the
        # row upload and the first program of the wave
        tok = self._enter("t_encode_ms", "raft-colocated-encode")
        G, M, E, P, B = self.capacity, self.M, self.E, self.P, self.budget
        if self._read_ctx_new:
            self._retake_lane_rows(batch, lane)
        lane.seal()
        enc = self._encode_generation(batch, lane.gs_np, lane.fed_np)
        tick_counts, sparse, staging, prop_rows, tick_fed = enc
        # the whole stepped set, active rows first and the lane after,
        # with the ticks each row was fed beside it
        n_act = len(batch)
        batch_gs = np.empty((n_act + len(lane),), np.int64)
        batch_gs[:n_act] = [g for _, g, _, _ in batch]
        batch_gs[n_act:] = lane.gs_np
        fed = np.zeros((len(batch_gs),), np.int64)
        fed[:n_act] = [tick_fed.get(g, 0) for _, g, _, _ in batch]
        fed[n_act:] = lane.fed_np
        if self._tables_dirty:
            self._rebuild_tables()
        # alive straight off the SoA lanes (attached & clean) — the old
        # per-launch Python scan over the whole meta table cost
        # ~0.5 µs/row (~125 ms/launch at 250k rows).  Stopping rows
        # must neither consume routed traffic nor be routable targets
        # (a stopped-but-undetached leader would keep winning device
        # elections while its host no longer publishes payloads to the
        # entry cache — healthy peers then fail-stop on
        # unreconstructible appends): STOPPED rows can never be
        # lane-alive because every stop path detaches first
        # (stop_shard/unregister, close/unregister_many, _halt_replica
        # all clear the lane before node.stop() runs); a STOPPING
        # not-yet-detached row is cleared here from this generation's
        # plan-loop observations, and for the at-most-one launch that
        # can race the detach's core-lock acquisition a stopping node
        # still merges and publishes payloads (see the stopping-row
        # merge contract below), so routed appends stay
        # reconstructible.
        alive_np = self._lanes.alive_mask()
        gen_stopping = getattr(self, "_gen_stopping", None)
        if gen_stopping:
            alive_np[gen_stopping] = False
        # raftlint: ignore[sync-budget] host-built index array, not a device readback
        prop_gs = np.asarray(prop_rows, np.int64)
        # ---- fused commit wave decision (ISSUE 15) ------------------
        # Chain K rounds device-side only when the generation's pending
        # work is ROUTABLE: there is multi-round work to do (proposals
        # riding this launch, or routed traffic already in flight whose
        # delivery spawns responses) and nothing in sight mutates
        # membership — stopping rows, deferred actions, quarantined
        # saves, quarantined row slots and escalation holds all fence
        # to the single-round path, which keeps the PR 11 detach-race
        # argument at its proven <=1-launch exposure (a K-round wave
        # would widen it to K).  Tick-only generations with an idle
        # route stay single-round: rounds 2..K would step an empty
        # inbox for every row.
        rounds = 1
        if self._fuse_rounds > 1 and (len(prop_gs) or self._pending_live):
            # multi-round work exists; fuse unless a fence condition
            # holds.  fused_fences counts ONLY this shape — routable
            # work forced single-round — so the stat carries fence
            # signal instead of drowning in idle tick generations
            # (review finding)
            if (
                not gen_stopping
                and not self._deferred
                and not self._free_pending
                and not self._save_quarantine
                and not self._lanes.esc_hold.any()
            ):
                rounds = self._fuse_rounds
                self.stats["fused_waves"] += 1
                self.stats["fused_rounds_stepped"] += rounds
            else:
                self.stats["fused_fences"] += 1
        # ONE upload and ONE program for everything the host tells the
        # device about this launch (see _pack_launch / _host_inbox)
        flat, nsb = _pack_launch(
            G, M, E, tick_counts, alive_np, batch_gs, prop_gs, sparse
        )
        if hostplane.PARITY:
            n_reads = self.stats["device_reads"]
            n_xfer = self.stats["device_transfers"]
            whole_batch = batch + self._lane_as_batch(lane)
            whole = self._encode_generation(whole_batch, (), ())
            self.stats["device_reads"] = n_reads
            self.stats["device_transfers"] = n_xfer
            hostplane.check_encode_parity(
                whole_batch, batch_gs, enc, whole, lane
            )
            # raftlint: ignore[sync-budget] host-built index array, not a device readback
            whole_props = np.asarray(whole.prop_rows, np.int64)
            hostplane.check_upload_parity(flat, _pack_launch(
                G, M, E, whole.tick_counts, alive_np, batch_gs,
                whole_props, whole.sparse,
            )[0])
        combo, host_inbox = self._run(
            _host_inbox, self._put(flat), G=G, M=M, E=E, NSB=nsb
        )

        old_state = self._state
        if self._pending is None:
            # a prior launch failure consumed the donated pending inbox
            # and could not rebuild it (see the handler below)
            self._pending = self._put_rows(make_inbox_packed(G, P * B, E))
        self._leave(tok)
        # dispatch: host time to enqueue the wave's programs (the device
        # runs them behind the host; its own time is in the trace)
        dispatching = self._phase("t_dispatch_ms")
        try:
            with _TraceAnnotation("raft-colocated-step"):
                # fused assemble+step with pending donated, and
                # new_state donated into route (dead after the merge):
                # minimizes per-generation device allocations (see
                # _assemble_and_step)
                new_state, out = self._run(
                    _assemble_and_step, old_state, host_inbox,
                    self._pending, combo, out_capacity=self.O,
                )
                merged, regions, stats_dev, packed_dev, flags_dev = (
                    self._run(
                        _route_step, old_state, new_state, out,
                        self._dest_dev, self._rank_dev, combo,
                        PB=P * B, E=E, budget=B,
                    )
                )
        except BaseException:
            # self._pending was DONATED above; leaving the deleted
            # buffer in place would poison every later generation with
            # "Array has been deleted" after one transient launch
            # failure (review finding).  Clear FIRST, then try to
            # rebuild — the rebuild itself allocates and can fail under
            # the same RESOURCE_EXHAUSTED this guards against, so a
            # None sentinel (rebuilt lazily at the next launch) must
            # never be skipped over.  Dropping the in-flight routed
            # traffic is raft-safe message loss.
            self._pending = None
            self._pending_live = False
            try:
                self._pending = self._put_rows(make_inbox_packed(G, P * B, E))
            except Exception:  # noqa: BLE001 — next launch rebuilds
                pass
            self._clock_unmerged(batch, lane)
            raise
        # from here the generation is the new device truth: the next
        # launch (possibly dispatched before this one merges) chains on
        # merged/regions.  A failure past this point poisons the chain
        # and takes the pipeline-reset recovery instead.
        self._pending = regions
        self._state = merged
        try:
            with _TraceAnnotation("raft-colocated-select"):
                # the wave's one commit-proving readback, requested NOW
                # and collected at merge time: flags + delivered +
                # counts + row ids + vals in each round's head, heavy
                # sections in its detail (see _select_and_blob).  Every
                # round's pair is requested at dispatch, so the whole
                # wave's blobs share ONE readback window while the host
                # assembles and dispatches the NEXT generation.
                caps = self._tier_caps(self._sel_tier)
                merged_l, out_l = [merged], [out]
                head_l, detail_l = [], []

                def _sel(merged_k, out_k, stats_k, packed_k, flags_k):
                    head_dev, detail_dev = self._run(
                        _select_and_blob, merged_k, out_k, stats_k,
                        packed_k, flags_k, combo, CAP_B=caps["b"],
                        CAP_SL=caps["sl"], CAP_N=caps["n"],
                        CAP_A=caps["a"], CAP_S=caps["s"], HOST_OFF=P * B,
                    )
                    head_dev.copy_to_host_async()
                    detail_dev.copy_to_host_async()
                    head_l.append(head_dev)
                    detail_l.append(detail_dev)

                _sel(merged, out, stats_dev, packed_dev, flags_dev)
                # ---- fused wave: rounds 2..K, dispatched back-to-back
                # with NO host sync between rounds.  Each round is the
                # exact single-round program chain (assemble over the
                # previous round's routed regions with the resident
                # EMPTY host inbox — ticks and proposals fed once, in
                # round 1 — then step, route, select), so a K-round
                # wave is bit-exact with K serial launches by
                # construction and reuses the warmed executables: no new
                # XLA programs, no tier recompiles (the r5 compile-time
                # finding rules out a monolithic K-round mega-program
                # here).
                for _k in range(1, rounds):
                    new_k, out_k = self._run(
                        _assemble_and_step, self._state, self._zero_host,
                        self._pending, combo, out_capacity=self.O,
                    )
                    merged_k, regions_k, stats_k, packed_k, flags_k = (
                        self._run(
                            _route_step, self._state, new_k, out_k,
                            self._dest_dev, self._rank_dev, combo,
                            PB=P * B, E=E, budget=B,
                        )
                    )
                    self._pending = regions_k
                    self._state = merged_k
                    merged_l.append(merged_k)
                    out_l.append(out_k)
                    _sel(merged_k, out_k, stats_k, packed_k, flags_k)
        except BaseException:
            self._clock_unmerged(batch, lane)
            self._reset_after_pipeline_failure()
            raise
        self._phase(dispatching)
        self.stats["launches"] += 1
        self.stats["device_steps"] += rounds
        self.stats["device_rows_stepped"] += len(batch_gs)
        self.stats["tick_lane_rows"] += len(lane)
        self.stats["device_rows_active"] += len(sparse)
        self._inflight.append(_InFlightGen(
            batch=batch, lane=lane, staging=staging, alive_np=alive_np,
            batch_gs=batch_gs, fed=fed, prop_gs=prop_gs, caps=caps,
            merged=merged_l, out=out_l, head_dev=head_l,
            detail_dev=detail_l, t_req=_time.monotonic(), rounds=rounds,
        ))

    def _parse_head(self, head, caps, G: int, nw: int):  # sync-hot
        """Host-side parse of one round's head blob (_select_and_blob's
        head layout): flags, packed delivered bits, route stats, the
        five section counts, the five selected-row-id sections and the
        values block."""
        flags = head[:G]
        delivered_bits = (
            head[G:G + G * nw].view(np.uint32).reshape(G, nw)
        )  # [G, ceil(O/32)] u32
        _parse = [G + G * nw]

        def take(n, shape=None):
            part = head[_parse[0]:_parse[0] + n]
            _parse[0] += n
            return part.reshape(shape) if shape is not None else part

        rstats = take(6)
        sel_counts = take(5)
        sel_rows = (
            take(caps["b"]), take(caps["sl"]), take(caps["n"]),
            take(caps["a"]), take(caps["s"]),
        )
        sel_vals = take(caps["s"] * N_VALS, (caps["s"], N_VALS))
        return flags, delivered_bits, rstats, sel_counts, sel_rows, sel_vals

    def _parse_detail(self, det, caps):  # sync-hot
        """Host-side parse of one round's detail blob, re-padding the
        routed-region slot columns the device omitted (always unused
        for slot bookkeeping — forwarded PROPOSE never rides the
        routed regions)."""
        O, W, M, E = self.O, self.W, self.M, self.E
        PB = self.P * self.budget
        _dp = [0]

        def dtake(n, shape):
            part = det[_dp[0]:_dp[0] + n]
            _dp[0] += n
            return part.reshape(shape)

        buf_np = dtake(
            caps["b"] * O * N_FIELDS_BUF, (caps["b"], O, N_FIELDS_BUF)
        )
        sel_slot_base = dtake(caps["sl"] * M, (caps["sl"], M))
        sel_slot_term = dtake(caps["sl"] * M, (caps["sl"], M))
        sel_ent_drop = dtake(caps["sl"] * M * E, (caps["sl"], M, E))
        need_np = dtake(caps["n"] * self.P, (caps["n"], self.P))
        ring_t = dtake(caps["a"] * W, (caps["a"], W))
        ring_c = dtake(caps["a"] * W, (caps["a"], W))
        slot_base = np.concatenate([
            np.full((caps["sl"], PB), SLOT_UNUSED_I, np.int32),
            sel_slot_base,
        ], axis=1)
        slot_term = np.concatenate([
            np.zeros((caps["sl"], PB), np.int32), sel_slot_term
        ], axis=1)
        ent_drop = np.concatenate([
            np.zeros((caps["sl"], PB, E), np.int32), sel_ent_drop
        ], axis=1)
        return (buf_np, slot_base, slot_term, ent_drop, need_np,
                ring_t, ring_c)

    def _merge_intermediate_round(  # sync-hot
        self, rec, rnd, caps, sets, flags, delivered_bits, sel_counts,
        sel_rows, sel_vals, needs_max, touched, esc_seen,
    ) -> None:
        """Merge ONE intermediate round of a fused wave, in two legs:

        * HEAVY rows (appends, host-visible outbox bytes, round-1
          proposal slots, snapshot-needing rows) take the per-row
          merge: scalar sync from THIS round's values, append
          reconstruction against THIS round's ring (entries published
          to the shard cache round-by-round so a receiver's round k+1
          reconstructs exactly as across k+1 serial launches), message
          attachment against THIS round's delivered bits.  Their ONE
          get_update rides the final round (``touched``).  The
          snapshot-need SECTION itself is final-round-only — the need
          flag re-fires while the condition persists (benign refire) —
          but need-flagged rows still sync state here.
        * every other row of the round's values block takes the LANE
          pass — the same ``_lane_commit_pass`` a single-round
          generation runs.  This is load-bearing, not an optimization:
          the flags word's F_CHANGED is a per-ROUND delta, so a commit
          advance or granted vote landing in an intermediate round is
          INVISIBLE to the final round's flags — only the lane diff
          (new words vs last HOST sync) sees it.  Skipping this leg
          stranded mid-wave commits' futures forever (found by the
          one-readback test's first soak)."""
        G = self.capacity
        n_buf_d, n_slot_d, n_need_d, n_append_d, n_sum_d = (
            int(x) for x in sel_counts
        )
        for key, need in (
            ("b", max(n_buf_d, len(sets.buf_rows))),
            ("sl", max(n_slot_d, len(sets.slot_rows))),
            ("n", max(n_need_d, len(sets.need_rows))),
            ("a", max(n_append_d, len(sets.append_rows))),
            ("s", max(n_sum_d, len(sets.sum_rows))),
        ):
            needs_max[key] = max(needs_max[key], need)
        slot_live = len(sets.slot_rows) if rnd == 0 else 0
        has_heavy = bool(
            len(sets.buf_rows) or len(sets.append_rows) or slot_live
        )
        if not has_heavy and not len(sets.sum_rows):
            # nothing host-visible happened this round: its detail
            # payload is never read (same contract as a pure
            # commit/tick generation)
            return
        tok = self._enter("t_updates_ms", "raft-colocated-updates")
        cover = self._sel_cover(
            G, caps,
            (n_buf_d, n_slot_d, n_need_d, n_append_d, n_sum_d),
            sel_rows, sets,
        )
        if cover is not None:
            pos_buf, pos_slot, pos_need, pos_ring, pos_sum, _src = cover
            vals_np = sel_vals[:n_sum_d]
            if has_heavy:
                det = self._collect_blob(rec.detail_dev[rnd], rec.t_req)
                (buf_np, slot_base, slot_term, ent_drop, _need_np,
                 ring_t, ring_c) = self._parse_detail(det, caps)
            else:
                buf_np = slot_base = slot_term = ent_drop = None
                ring_t = ring_c = None
        else:
            # exact host-side selection for this round (capacity
            # overflow): one extra sync round trip, charged one fresh
            # floor — identical to the single-round fallback
            self.stats["sel_fallbacks"] = (
                self.stats.get("sel_fallbacks", 0) + 1
            )
            self.stats["readback_windows"] += 1
            idx4 = _build_idx4(
                sets.buf_rows.tolist(), sets.slot_rows.tolist(),
                sets.need_rows.tolist(), sets.append_rows.tolist(),
            )
            _tq = _time.monotonic()
            detail, vals_np = _fetch_detail_vals(
                self, rec.merged[rnd], rec.out[rnd], idx4,
                sets.sum_rows.tolist(), self.O,
                self.M + self.P * self.budget, self.E, self.P, self.W,
                allow_fused=False,
            )
            self._floor_wait(_tq)
            if detail is not None:
                (buf_np, slot_base, slot_term, ent_drop, _need_np,
                 ring_t, ring_c) = detail
            else:
                buf_np = slot_base = slot_term = ent_drop = None
                ring_t = ring_c = None
            pos_buf = hostplane.pos_of(G, sets.buf_rows)
            pos_ring = hostplane.pos_of(G, sets.append_rows)
            pos_slot = hostplane.pos_of(G, sets.slot_rows)
            pos_need = hostplane.pos_of(G, sets.need_rows)
            pos_sum = hostplane.pos_of(G, sets.sum_rows)
        from .engine import SLOT_DROPPED

        stage_map = rec.staging if rnd == 0 else {}
        vals_l = vals_np.tolist() if vals_np is not None else None
        heavy_gs = set(sets.buf_rows.tolist())
        heavy_gs.update(sets.append_rows.tolist())
        # need-flagged rows sync state here (their SECTION waits for
        # the final round — benign refire); without this a
        # need-only row's mid-wave state change would strand like any
        # other non-final F_CHANGED
        heavy_gs.update(sets.need_rows.tolist())
        if rnd == 0:
            heavy_gs.update(sets.slot_rows.tolist())
        for g in sorted(heavy_gs):
            meta = self._meta.get(g)
            if meta is None or meta.node.stopped or vals_l is None:
                continue
            node = meta.node
            r = node.peer.raft
            base = int(self._base[g])
            k = int(pos_sum[g])
            if k < 0:
                continue  # heavy rows always carry values; defense
            sv = vals_l[k]
            term, vote, committed, leader, role, last = sv[:6]
            committed += base
            last += base
            # scalar sync BEFORE the merge — same order as the final
            # round's loop (see the noop-barrier note there)
            r.term, r.vote, r.leader_id = term, vote, leader
            r.role = _ROLE_OF[role]
            if node.device_forwarded_reads:
                node.check_forwarded_reads(term, leader)
            if (flags[g] & _F_APPEND) and int(pos_ring[g]) >= 0:
                try:
                    stamped = self._merge_appends(
                        r, g, int(sv[_R_APPEND_LO]) + base, last,
                        stage_map.get(g, {}),
                        int(pos_slot[g]) if rnd == 0 else -1,
                        slot_base, slot_term, ent_drop,
                        ring_t[int(pos_ring[g])],
                        ring_c[int(pos_ring[g])],
                        fallback=self._cache_lookup,
                        barrier=(
                            int(sv[_R_BARRIER_IDX]) + base,
                            int(sv[_R_BARRIER_TERM]),
                        ),
                        base=base,
                    )
                except RuntimeError:
                    od = self._entry_cache.get(r.shard_id)
                    _log.critical(
                        "[%d:%d] routed append reconstruction failed "
                        "in fused round %d; halting replica (cache "
                        "keys tail: %s)",
                        r.shard_id, r.replica_id, rnd,
                        list(od.keys())[-12:] if od else [],
                        exc_info=True,
                    )
                    self._halt_replica(g)
                    continue
                self._cache_put(r.shard_id, stamped)
            if committed > r.log.committed:
                r.log.commit_to(committed)
            if (
                role != int(RaftRole.LEADER)
                and node.device_reads.has_pending()
            ):
                node.drop_device_reads()
            if int(pos_buf[g]) >= 0 and buf_np is not None:
                bits = delivered_bits[g]
                dr = (
                    (bits[self._dw_word] >> self._dw_shift) & 1
                ).astype(bool)
                self._attach_messages(
                    r, node, buf_np[int(pos_buf[g])], int(sv[_R_COUNT]),
                    stage_map.get(g, {}), delivered_row=dr, base=base,
                )
            sk = int(pos_slot[g]) if rnd == 0 else -1
            if sk >= 0 and slot_base is not None:
                sb = slot_base[sk]
                drop = ent_drop[sk]
                for slot, ents in stage_map.get(g, {}).items():
                    if sb[slot] == SLOT_DROPPED:
                        r.dropped_entries.extend(ents)
                    elif sb[slot] >= 0:
                        r.dropped_entries.extend(
                            e for i_e, e in enumerate(ents)
                            if drop[slot, i_e]
                        )
            touched[g] = node
        # ---- lane leg: every OTHER row with values this round --------
        # The same lane commit pass a single-round generation runs —
        # heavy rows fall out of its eligibility mask by construction
        # (append flag / buf / slot / need positions), rows already
        # deferred to escalation recovery are excluded, and rows it
        # syncs update the lanes so the NEXT round's diff composes.
        if vals_np is not None and len(sets.sum_rows):
            live_k = self._live_rows(rec, flags, sets, esc_seen, True)
            self.stats["completion_rows_walked"] += len(live_k)
            if hostplane.PARITY:
                hostplane.check_completion_parity(
                    hostplane.CompletionTrace(emitted=_emitting(
                        live_k, pos_sum, ())),
                    hostplane.CompletionTrace(emitted=_emitting(
                        self._live_rows_whole(rec, sets, esc_seen, True),
                        pos_sum, ())),
                )
            pos_slot_k = (
                pos_slot if rnd == 0
                else hostplane.pos_of(G, sets.slot_rows)
            )
            self._lane_commit_pass(
                live_k, flags, pos_sum, pos_buf, pos_slot_k, pos_need,
                vals_np, np.zeros((len(live_k),), bool),
            )
            # bulk mirror + update-lane write for the round's sum rows
            # — the final round's bulk write only covers rows flagged
            # in the FINAL round, and F_CHANGED is a per-round delta:
            # without this, a leader elected mid-wave left a
            # permanently stale leader=0 mirror, which blocked quiesce
            # parking on the whole shard (found by test_scale's
            # cold-kill gate).  Lane-pass rows were already written —
            # identical values, idempotent; heavy rows sync here.
            gs_sum = sets.sum_rows
            sum_pos = pos_sum[gs_sum]
            ok = sum_pos >= 0
            if ok.any():
                gs_ok = gs_sum[ok].astype(np.int64)
                w = vals_np[sum_pos[ok], :6].T
                # lease arm/disarm on role transitions observed THIS
                # round, probed against the PRE-write mirror — the
                # final _lease_pass compares against the mirror too,
                # and this write is about to refresh it, so a mid-wave
                # election win would otherwise never arm its lease
                # (found by test_device_lease_reads_colocated: a
                # resident leader whose win landed inside a wave held
                # lease 0 forever)
                chg = np.nonzero(
                    w[_R_ROLE] != self._mirror[_R_ROLE, gs_ok]
                )[0]
                for i in chg.tolist():
                    g2 = int(gs_ok[i])
                    meta2 = self._meta.get(g2)
                    if meta2 is None or meta2.node.stopped:
                        continue
                    r2 = meta2.node.peer.raft
                    if (
                        int(w[_R_ROLE, i]) == _ROLE_LEADER_I
                        and r2.check_quorum
                    ):
                        self._lease.arm(g2, r2.election_timeout)
                    else:
                        self._lease.disarm(g2)
                self._mirror[:6, gs_ok] = w
                w_abs = w.astype(np.int64)
                b_abs = self._base[gs_ok]
                w_abs[_R_COMMIT] += b_abs
                w_abs[_R_LAST] += b_abs
                self._ulanes.words[:, gs_ok] = w_abs
        self._leave(tok)

    def _complete_generation(self, rec: _InFlightGen) -> List[Tuple]:  # sync-hot
        """Merge one in-flight generation: collect each round's head
        (the earliest commit-proving sync), complete commit-only rows
        straight off the FINAL round's head, and read detail payloads
        (all in flight since dispatch) only for rounds with heavy
        sections.  A fused wave (rec.rounds > 1, ISSUE 15) unpacks its
        per-round delivered bits and heavy sections round by round —
        intermediate rounds merge appends/outboxes/round-1 slots into
        the scalar rafts, the final round runs the full single-round
        tail (lease, bookkeeping, lane commit pass, get_update) over
        the wave's end state, so every row emits at most ONE update
        per wave.  Caller holds the core lock; generations complete in
        dispatch order (_complete_oldest)."""
        G, M, E, P, B = self.capacity, self.M, self.E, self.P, self.budget
        batch, staging, caps = rec.batch, rec.staging, rec.caps
        alive_np, batch_gs, prop_gs = (
            rec.alive_np, rec.batch_gs, rec.prop_gs
        )
        K = rec.rounds
        nw = (self.O + 31) // 32
        updates: List[Tuple] = []
        esc_seen: set = set()
        # rows whose scalar state an intermediate round already
        # mutated: they owe ONE get_update at the end of the wave even
        # if the final round left them quiet
        touched: Dict[int, object] = {}
        needs_max = {"b": 0, "sl": 0, "n": 0, "a": 0, "s": 0}
        empty_gs = np.zeros((0,), np.int64)
        # ONE readback window per generation: every round's blobs were
        # requested together at dispatch and share rec.t_req, so the
        # first collect pays the floor remainder and the rest land in
        # the same round trip — the one-readback-per-wave budget the
        # fused-round smoke asserts
        self.stats["readback_windows"] += 1
        for rnd in range(K):
            final = rnd == K - 1
            round_props = prop_gs if rnd == 0 else empty_gs
            tok = self._enter("t_dev_blob_ms", "raft-colocated-readback")
            _tc = _time.monotonic()
            head = self._collect_blob(rec.head_dev[rnd], rec.t_req)
            self._leave(tok)
            # merge: parse the head, classify the round's rows, list
            # the live ones — up to where the detail pass starts
            tok = self._enter("t_merge_ms", "raft-colocated-merge")
            if rnd == 0 and self._pipeline_depth > 1:
                # host-side work done between the D2H request
                # (dispatch) and this collect ran concurrently with
                # the readback — the double-buffering win, visible
                # without hardware
                overlap = max(0.0, _tc - rec.t_req)
                if self._sync_floor_s > 0:
                    overlap = min(overlap, self._sync_floor_s)
                self.stats["pipeline_overlap_s"] += overlap
            (flags, delivered_bits, rstats, sel_counts, sel_rows,
             sel_vals) = self._parse_head(head, caps, G, nw)
            (sel_rows_buf, sel_rows_slot, sel_rows_need,
             sel_rows_append, sel_rows_sum) = sel_rows
            if final:
                self._behind = (flags & _F_PEERS_BEHIND) != 0
                self._pending_live = int(rstats[0]) > 0
            self.stats["routed_delivered"] += int(rstats[0])
            self.stats["routed_dropped"] += int(
                rstats[1] + rstats[2] + rstats[3]
            )
            # per-cause breakdown (RouteStats order; r4 verdict weak
            # #5: the aggregate hid which drop class dominates)
            self.stats["routed_dropped_off_device"] = self.stats.get(
                "routed_dropped_off_device", 0
            ) + int(rstats[1])
            self.stats["routed_dropped_budget"] = self.stats.get(
                "routed_dropped_budget", 0
            ) + int(rstats[2])
            self.stats["routed_dropped_ring"] = self.stats.get(
                "routed_dropped_ring", 0
            ) + int(rstats[3])

            # ---- merge row sets (array-at-once) ----------------------
            # ONE vectorized pass over the [G] flags word classifies
            # every row of the round (ops/hostplane.py).  The scalar
            # twins remain the parity oracle
            # (DRAGONBOAT_TPU_HOSTPLANE_PARITY runs both every round).
            sets = hostplane.build_merge_sets(
                flags, alive_np, batch_gs, round_props, G=G
            )
            hostplane.record_generation(
                flags, alive_np, batch_gs, round_props, G
            )
            if hostplane.PARITY:
                hostplane.check_merge_parity(
                    flags, alive_np, batch_gs, round_props, sets, G=G
                )

            # ---- escalations: DEFERRED to the pipeline drain ---------
            # The device already restored escalated rows (suppress mask
            # in _route_step) and suppressed their outboxes; later
            # rounds/generations re-stepped them from the restored
            # state, so the recovery (evict + scalar replay) runs only
            # at depth 0 (see _apply_escalation).  A wave records each
            # escalated row ONCE: the batch inputs are replayed only
            # when round 1 suppressed them — a row escalating first in
            # a LATER round consumed its inputs in round 1, so only
            # the routed-only (input-less) recovery applies, exactly
            # the cross-generation contract.
            n_esc = len(sets.esc_batch_pos) + len(sets.esc_other)
            if n_esc:
                self.stats["escalations"] += n_esc
                for i in sets.esc_batch_pos.tolist():
                    # a position past the active rows names a lane row
                    g = int(batch_gs[i])
                    if g in esc_seen:
                        continue
                    esc_seen.add(g)
                    node, _g, si = rec.stepped_row(i)
                    self._deferred.append(
                        ("esc", node, g, si if rnd == 0 else None)
                    )
                for g in sets.esc_other.tolist():
                    if g in esc_seen:
                        continue
                    meta = self._meta.get(g)
                    if meta is not None:
                        esc_seen.add(g)
                        # routed-only inputs: discarded (raft-safe)
                        self._deferred.append(("esc", meta.node, g, None))

            if not final:
                self._merge_intermediate_round(
                    rec, rnd, caps, sets, flags, delivered_bits,
                    sel_counts, sel_rows, sel_vals, needs_max, touched,
                    esc_seen,
                )
                self._leave(tok)
                continue

            # ================= FINAL round ===========================
            break  # fall through to the final-round tail below

        stage_map = staging if K == 1 else {}
        rnd = K - 1
        # ---- live rows: the rows this completion walks in Python ------
        # The active batch rows, the lane rows the final round's flags
        # mark, and any resident row with effects — every batch row
        # whose device row escalated in ANY round of the wave excluded
        # (its recovery is the deferred evict+replay above).  The lane
        # rows that only ticked are clocked and leased as arrays below.
        live = self._live_rows(rec, flags, sets, esc_seen, False)
        live_gs = {g for _, g, _ in live}
        # rows an intermediate round touched that the final round left
        # quiet still owe their get_update (merged appends/messages
        # must persist and dispatch)
        for g, node in touched.items():
            if g not in live_gs and g not in esc_seen:
                live.append((node, g, None))
                live_gs.add(g)
        self.stats["completion_rows_walked"] += len(live)
        whole = None
        if hostplane.PARITY:
            # the live list as it was before PR 29, one tuple a stepped
            # row: what the per-row passes are worked out over
            whole = self._live_rows_whole(rec, sets, esc_seen, False)
            whole_gs = {g for _, g, _ in whole}
            whole += [
                (node, g, None) for g, node in touched.items()
                if g not in whole_gs and g not in esc_seen
            ]

        buf_rows = sets.buf_rows
        append_rows = sets.append_rows
        slot_rows = sets.slot_rows
        need_rows = sets.need_rows
        sum_rows = sets.sum_rows
        n_buf_d, n_slot_d, n_need_d, n_append_d, n_sum_d = (
            int(x) for x in sel_counts
        )
        self._leave(tok)
        tok = self._enter("t_detail_ms", "raft-colocated-detail")
        # device-selected detail (the split-blob fast path): the head
        # already carries counts/row-ids/vals for the rows the DEVICE
        # selected with the same flag logic; verify the host's sets are
        # covered and fall back to an exact two-sync gather when not
        # (capacity overflow, or a row the device's live approximation
        # missed).  Coverage and row->gather-position maps are index
        # arrays (hostplane.pos_of/covered) — the old per-row dict
        # builds and `all(g in …)` membership scans were O(rows) Python
        cover = self._sel_cover(
            G, caps,
            (n_buf_d, n_slot_d, n_need_d, n_append_d, n_sum_d),
            (sel_rows_buf, sel_rows_slot, sel_rows_need,
             sel_rows_append, sel_rows_sum),
            sets,
        )
        dev_ok = cover is not None
        early_done = np.zeros((len(live),), bool)
        lease_done = False
        if dev_ok:
            pos_buf, pos_slot, pos_need, pos_ring, pos_sum, sum_src = cover
            if K > 1:
                # the DEVICE's slot selection keys off the wave-wide
                # prop mask (combo rides every round), but host slot
                # bookkeeping is round-1-only and round 1's
                # intermediate merge already consumed it — the final
                # round's host semantics (empty slot set) rule, or the
                # loop would index slot sections it never collected
                pos_slot = hostplane.pos_of(G, slot_rows)
            # live rows only: the padded capacity tail is garbage the
            # merge loop never indexes, and converting it cost tens of
            # ms/launch at storm-tier capacities (review finding)
            sel_vals = sel_vals[:n_sum_d]
            vals_np = sel_vals
            # lease pass BEFORE bookkeeping: the lease anchor is the
            # PRE-launch clock (see _lease_pass); then ONE batched
            # bookkeeping pass for the whole generation
            self._clock_and_lease(
                rec, live, flags, vals_np, pos_sum, esc_seen, touched,
                whole,
            )
            lease_done = True
            # ---- EARLY completion: the commit-proving prefix --------
            # A live row with values but NO append/outbox/slot/need
            # sections (the common shape: a leader whose routed acks
            # just advanced commit, a follower applying) needs nothing
            # from the detail payload — the LANE pass diffs its words
            # against the update lanes, syncs only what moved and
            # persists the whole set in one batched lane save NOW, so
            # proposals complete from the earliest sync that proves
            # their commit instead of waiting for the detail to land
            # and the heavy merge tail to run.
            self._lane_commit_pass(
                live, flags, pos_sum, pos_buf, pos_slot, pos_need,
                vals_np, early_done,
            )
            need_detail = bool(
                len(buf_rows) or len(append_rows)
                or len(slot_rows) or len(need_rows)
            )
            if need_detail:
                det = self._collect_blob(rec.detail_dev[rnd], rec.t_req)
                (buf_np, slot_base, slot_term, ent_drop, need_np,
                 ring_t, ring_c) = self._parse_detail(det, caps)
            else:
                # pure commit/tick generation: the detail payload is
                # never read — on hardware its bytes still rode the
                # same round trip, and nothing here waits for them
                buf_np = slot_base = slot_term = ent_drop = None
                need_np = ring_t = ring_c = None
        else:
            # exact host-side selection (the r5 two-sync path) — an
            # extra sync round trip; the floor shim charges it one
            # fresh floor from request time
            self.stats["sel_fallbacks"] = (
                self.stats.get("sel_fallbacks", 0) + 1
            )
            self.stats["readback_windows"] += 1
            idx4 = _build_idx4(
                buf_rows.tolist(), slot_rows.tolist(),
                need_rows.tolist(), append_rows.tolist(),
            )
            _tq = _time.monotonic()
            # the kernel ran on the ASSEMBLED inbox (host slots + routed
            # regions), so the out slot arrays are M + P*B wide
            detail, vals_np = _fetch_detail_vals(
                self, rec.merged[rnd], rec.out[rnd], idx4, sum_rows.tolist(),
                self.O, M + P * B, E, P, self.W, allow_fused=False,
            )
            self._floor_wait(_tq)
            if detail is not None:
                (buf_np, slot_base, slot_term, ent_drop, need_np, ring_t,
                 ring_c) = detail
            else:
                buf_np = slot_base = slot_term = ent_drop = need_np = None
                ring_t = ring_c = None
            # position maps over the HOST-ordered gather sections (the
            # same order _build_idx4 packed them in)
            pos_buf = hostplane.pos_of(G, buf_rows)
            pos_ring = hostplane.pos_of(G, append_rows)
            pos_slot = hostplane.pos_of(G, slot_rows)
            pos_need = hostplane.pos_of(G, need_rows)
            pos_sum = hostplane.pos_of(G, sum_rows)
            sum_src = sum_rows
        # tier selection: promote immediately to the smallest warmed
        # tier that fits this generation's needs — the max over EVERY
        # round of the wave (overflow used the exact fallback above,
        # once per overflowing round); demote only after 64
        # consecutive launches that would have fit the lower tier
        needs = {
            "b": max(needs_max["b"], n_buf_d, len(buf_rows)),
            "sl": max(needs_max["sl"], n_slot_d, len(slot_rows)),
            "n": max(needs_max["n"], n_need_d, len(need_rows)),
            "a": max(needs_max["a"], n_append_d, len(append_rows)),
            "s": max(needs_max["s"], n_sum_d, len(sum_rows)),
        }
        need_tier = len(_SEL_TIERS) - 1
        for t in range(len(_SEL_TIERS)):
            c = self._tier_caps(t)
            if all(needs[k] <= c[k] for k in c):
                need_tier = t
                break
        if need_tier > self._sel_tier:
            self._sel_tier = need_tier
            self._sel_fit_streak = 0
        elif need_tier < self._sel_tier:
            self._sel_fit_streak += 1
            if self._sel_fit_streak >= 64:
                self._sel_tier = need_tier
                self._sel_fit_streak = 0
        else:
            self._sel_fit_streak = 0
        # device-plane lease evidence: age every armed stepped row by
        # its ticks and start the age over where the quorum-fresh flag
        # holds (hostplane.LeaseAges) — BEFORE the bulk mirror write
        # below so role transitions are still observable.  The dev_ok
        # path already ran this pass (pre-early-commit, so the anchor
        # is the pre-launch clock); running it again would age every
        # lease twice.  On the exact-fallback path the bookkeeping +
        # lane passes run here instead (detail and position maps only
        # just landed) — same order as dev_ok: lease, bookkeeping, lane
        # commit.
        if not lease_done:
            self._clock_and_lease(
                rec, live, flags, vals_np, pos_sum, esc_seen, touched,
                whole,
            )
            if vals_np is not None:
                self._lane_commit_pass(
                    live, flags, pos_sum, pos_buf, pos_slot, pos_need,
                    vals_np, early_done,
                )
        self._leave(tok)
        tok = self._enter("t_updates_ms", "raft-colocated-updates")
        # one C-level conversion for the merge loop's 10-ints-per-row
        # reads (numpy scalar -> int costs ~100 ns each)
        vals_l = vals_np.tolist() if vals_np is not None else None

        from .engine import SLOT_DROPPED

        # ---- per-row effect merge, batch-indexed ---------------------
        # Everything the loop used to look up per row (gather positions
        # via the *_at dicts, flag probes, bases, delivered-bit unpack,
        # limit checks, mirror writes) is gathered ONCE here over the
        # [*, G] arrays; the residual per-row body below only mutates
        # the Python raft objects it must (scalar sync, append merge,
        # update construction) — see ops/hostplane.py.
        # raftlint: ignore[sync-budget] host-built index array, not a device readback
        gs_m = np.asarray([g for _, g, _ in live], np.int64)
        n_live = len(gs_m)
        if n_live:
            sum_k = pos_sum[gs_m]
            buf_k = pos_buf[gs_m]
            slot_k = pos_slot[gs_m]
            need_k = pos_need[gs_m]
            ring_k = pos_ring[gs_m]
            app_l = ((flags[gs_m] & _F_APPEND) != 0).tolist()
            bases_l = self._base[gs_m].tolist()
            sum_k_l = sum_k.tolist()
            buf_k_l = buf_k.tolist()
            slot_k_l = slot_k.tolist()
            need_k_l = need_k.tolist()
            ring_k_l = ring_k.tolist()
            # delivered bits unpacked for ALL buf rows in one shot (the
            # per-row word/shift unpack cost ~1-2 µs a row)
            has_buf = buf_k >= 0
            nb = int(has_buf.sum())
            if nb:
                bits = delivered_bits[gs_m[has_buf]]
                dr_pack = (
                    (bits[:, self._dw_word] >> self._dw_shift) & 1
                ).astype(bool)
                dr_at = np.full((n_live,), -1, np.int32)
                dr_at[has_buf] = np.arange(nb, dtype=np.int32)
                dr_at_l = dr_at.tolist()
            # bulk mirror write for every row the loop will merge
            # (rows it then skips — stopped/halted — are freed and
            # re-seeded at their next upload, so the write is moot)
            in_sum = sum_k >= 0
            if vals_np is not None and in_sum.any():
                self._mirror[:6, gs_m[in_sum]] = (
                    vals_np[sum_k[in_sum], :6].T
                )
                # update lanes follow for the HEAVY rows the loop below
                # syncs per-row (lane-pass rows were already written —
                # identical values, idempotent), absolute frame: the
                # next generation's lane diff must see what was synced
                w_abs = vals_np[sum_k[in_sum], :6].T.astype(np.int64)
                b_abs = self._base[gs_m[in_sum]]
                w_abs[_R_COMMIT] += b_abs
                w_abs[_R_LAST] += b_abs
                self._ulanes.words[:, gs_m[in_sum]] = w_abs
        if vals_np is not None and len(sum_src):
            # fast-lane invalidation, batch-wide: rows approaching an
            # int32 lane limit or streaming a snapshot re-run the full
            # plan (the only plan facts a DEVICE step can change;
            # everything else arrives via the host queues, which the
            # fast lane checks each launch).  Safe-side: clearing
            # plan_ok for a row the loop later skips only forces one
            # extra full plan.  (The fallback gather pads vals to a
            # bucket; only the first len(sum_src) rows are real.)
            v = vals_np[: len(sum_src)]
            over = (
                (v[:, _R_TERM] > _LIM_SOFT) | (v[:, _R_LAST] > _LIM_SOFT)
            )
            if over.any():
                # raftlint: ignore[sync-budget] host numpy row ids, not a device readback
                self._lanes.plan_ok[np.asarray(sum_src)[over]] = False
        if len(need_rows):
            self._lanes.plan_ok[need_rows] = False
        # (g, p, lane-or-None, pid, ss_index) — see _send_snapshots
        snapshot_sends: List[Tuple[int, int, Optional[int], int, int]] = []
        for j, (node, g, si) in enumerate(live):
            if early_done[j]:
                continue  # fully handled by the early commit pass
            # a STOPPING node still merges and persists this launch's
            # results: its device acks were already routed to peers in
            # this very launch, and dropping the corresponding append
            # persist would let an acked entry vanish on restart — the
            # follower then wedges forever on the by-design
            # reject<=match floor (r4 chaos finding: kill racing a
            # launch left a replica acked-at-23 with a WAL at 22).
            # Only truly STOPPED nodes (logdb closing) are skipped; the
            # alive mask already keeps stopping rows out of the NEXT
            # launch.
            if node.stopped or self._meta.get(g) is None:
                continue
            r = node.peer.raft
            base = bases_l[j]  # the shard's shared base
            # (tick bookkeeping already ran in _bookkeeping_pass)
            k = sum_k_l[j]
            if k < 0:
                # no final-round flags, no slots — but a row an
                # intermediate round of the wave touched (merged
                # appends, attached messages, dropped slots) still
                # owes its ONE wave-end update: the scalar sync ran in
                # its last heavy round, so only the emission remains
                if g in touched:
                    u = node.peer.get_update(
                        last_applied=node.sm.last_applied
                    )
                    node.dispatch_dropped(u)
                    updates.append((node, u))
                    node._check_leader_change()
                # else: the row only ticked
                continue
            sv = vals_l[k]
            term, vote, committed, leader, role, last = sv[:6]
            committed += base
            last += base
            # scalar sync BEFORE the merge: the noop-barrier-vs-lost-
            # payload distinction in _merge_appends needs the POST-step
            # role (a row that just won its election self-appends the
            # barrier; its host mirror still says candidate)
            r.term, r.vote, r.leader_id = term, vote, leader
            r.role = RaftRole(role)
            if node.device_forwarded_reads:
                node.check_forwarded_reads(term, leader)
            if app_l[j]:
                try:
                    stamped = self._merge_appends(
                        r, g, int(sv[_R_APPEND_LO]) + base, last,
                        stage_map.get(g, {}), slot_k_l[j], slot_base,
                        slot_term, ent_drop, ring_t[ring_k_l[j]],
                        ring_c[ring_k_l[j]],
                        fallback=self._cache_lookup,
                        barrier=(
                            int(sv[_R_BARRIER_IDX]) + base,
                            int(sv[_R_BARRIER_TERM]),
                        ),
                        base=base,
                    )
                except RuntimeError:
                    # fail-stop THIS replica only (divergence policy);
                    # aborting the loop would strand every other row's
                    # merge and spread the inconsistency
                    od = self._entry_cache.get(r.shard_id)
                    _log.critical(
                        "[%d:%d] routed append reconstruction failed; "
                        "halting replica (cache keys tail: %s)",
                        r.shard_id, r.replica_id,
                        list(od.keys())[-12:] if od else [],
                        exc_info=True,
                    )
                    self._halt_replica(g)
                    continue
                self._cache_put(r.shard_id, stamped)
            if committed > r.log.committed:
                r.log.commit_to(committed)
            if (
                role != int(RaftRole.LEADER)
                and node.device_reads.has_pending()
            ):
                node.drop_device_reads()
            if buf_k_l[j] >= 0:
                self._attach_messages(
                    r, node, buf_np[buf_k_l[j]], int(sv[_R_COUNT]),
                    stage_map.get(g, {}), delivered_row=dr_pack[dr_at_l[j]],
                    base=base,
                )
            sk = slot_k_l[j]
            if sk >= 0:
                sb = slot_base[sk]
                drop = ent_drop[sk]
                for slot, ents in stage_map.get(g, {}).items():
                    if sb[slot] == SLOT_DROPPED:
                        r.dropped_entries.extend(ents)
                    elif sb[slot] >= 0:
                        r.dropped_entries.extend(
                            e for i_e, e in enumerate(ents)
                            if drop[slot, i_e]
                        )
            if need_k_l[j] >= 0:
                self._send_snapshots(r, g, need_np[need_k_l[j]],
                                     snapshot_sends)
            u = node.peer.get_update(last_applied=node.sm.last_applied)
            node.dispatch_dropped(u)
            updates.append((node, u))
            node._check_leader_change()
        self._leave(tok)

        # applied to the CURRENT state handle — possibly one generation
        # past the one that flagged the need.  Benign: the need flag
        # re-fires while the condition persists, the lane write is
        # idempotent, and at most one extra probe volley reaches a peer
        # already being streamed to
        self._mark_remote_snapshots(snapshot_sends)
        below = [t for t in snapshot_sends if t[2] is None]
        if below:
            # the durable snapshot sits below the shard base (see
            # VectorStepEngine._send_snapshots): these rows take a host
            # excursion — a membership mutation, so it runs at the next
            # depth-0 point (_apply_snapshot_below), never mid-merge
            self._deferred.append(("below", below))

        if self._pending_live:
            # in-flight routed traffic: wake every ALIVE resident
            # node's engine so some worker launches again and the
            # messages are consumed
            tok = self._enter("t_wake_ms", "raft-colocated-wake")
            self._wake_alive()
            self._leave(tok)
        return updates


# what _ColocatedFacade.totals() counts, name -> (stats key, scale):
# ExecEngine.APPLY_TOTALS, seconds folded in as ms, the NodeHost's
# leader_changes and its request.HOST_TOTALS
_MEMBER_TOTALS = {
    name: (name[:-1] + "ms", 1000.0) if name.startswith("t_") else (name, 1)
    for name in (*APPLY_TOTALS, "leader_changes", *HOST_TOTALS)
}


class _ColocatedFacade(IStepEngine):
    """Per-NodeHost view of the shared core (the IStepEngine each
    ExecEngine drives).  Tracks shard -> replica so ``detach(shard_id)``
    — the IStepEngine contract — releases only THIS host's replica."""

    def __init__(self, core: ColocatedVectorEngine, nodehost=None):
        self.core = core
        self._nodehost = nodehost
        # its totals as of the core's last _fold_apply (written there,
        # under the core lock)
        self.folded: Dict[str, float] = {}
        self._replica_of: Dict[int, int] = {}

    @property
    def stats(self):
        return self.core.stats

    def step_shards(self, nodes, worker_id: int) -> None:
        for n in nodes:
            self._replica_of[n.shard_id] = n.replica_id
        self.core.step_shards(nodes, worker_id)

    def totals(self) -> dict:
        """This member's ``ExecEngine.apply_totals()``, its NodeHost's
        ``leader_changes`` and ``host_totals``, by the names of
        ``_MEMBER_TOTALS``; nothing until the NodeHost has built its
        engine (the factory runs before that)."""
        nh = self._nodehost
        engine = getattr(nh, "engine", None)
        if engine is None:
            return {}
        return {**engine.apply_totals(), "leader_changes": nh.leader_changes,
                **nh.host_totals.snapshot()}

    def stop(self) -> None:
        # the member is going away: take its last totals, then forget it
        core = self.core
        with core._lock:
            core._fold_apply()
            if self in core._members:
                core._members.remove(self)
                if not core._members:
                    core.close()

    def device_coordinate(self, shard_id: int):
        return self.core.device_coordinate(
            shard_id, self._replica_of.get(shard_id)
        )

    def device_chip_count(self) -> int:
        return self.core.device_chip_count()

    def detach(self, shard_id: int) -> None:
        rid = self._replica_of.pop(shard_id, None)
        if rid is not None:
            self.core.detach_replica(shard_id, rid)

    def detach_many(self, shard_ids) -> None:
        pairs = []
        for s in shard_ids:
            rid = self._replica_of.pop(s, None)
            if rid is not None:
                pairs.append((s, rid))
        if pairs:
            self.core.detach_replicas(pairs)


class ColocatedEngineGroup:
    """Product plug point: one group per colocated cluster.

        group = ColocatedEngineGroup(capacity=64, P=5, budget=2)
        cfg.expert.step_engine_factory = group.factory   # every member
    """

    def __init__(self, **kw):
        self._kw = kw
        self._core: Optional[ColocatedVectorEngine] = None
        self._lock = threading.Lock()

    @property
    def core(self) -> Optional[ColocatedVectorEngine]:
        return self._core

    def factory(self, nodehost) -> _ColocatedFacade:
        with self._lock:
            if self._core is None:
                self._core = ColocatedVectorEngine(**self._kw)
            core = self._core
        facade = _ColocatedFacade(core, nodehost)
        if nodehost is not None:
            with core._lock:
                core._members.append(facade)
                core._arm_gc()  # again, if its last member closed it
        return facade
