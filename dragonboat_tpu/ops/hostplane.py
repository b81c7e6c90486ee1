"""Array-at-once host-plane machinery for the colocated launch path.

An r5 run of Config 4 (50k shards, mixed 3/5/7) showed that at 250k
replica rows the DEVICE plane costs ~4 s of a 2,731 s 50k-shard
election while ``t_plan`` (887 s) and ``t_updates`` (538 s) — per-row
Python in the colocated engine's plan and merge stages — dominate.
This module is the fix: the per-row work that is pure *metadata math*
(eligibility classification, merge row-set construction, coverage
checks, index maps) runs as numpy array ops over ALL rows per
generation instead of per-row attribute probes and dict builds.

Three layers:

* ``RowLanes`` — the SoA truth store for per-row engine metadata
  (``attached``/``dirty``/``plan_ok``/``esc_hold``).  The per-row
  ``_RowMeta`` objects in ``ops/engine.py`` are thin property views
  over these lanes, so every existing scalar path keeps its field
  syntax while the vectorized passes read whole lanes at once.

* vectorized passes — ``classify_static`` (the batched plan
  classifier's static-eligibility prefilter), ``encode_tick_lane``
  (the launch's tick-only rows, encoded from two index/count lists
  and never as ``Message`` objects), ``build_merge_sets``
  (the post-launch row sets: escalations, live rows, buf/append/
  need/slot/sum), ``pos_of``/``covered`` (index-array replacements
  for the old per-row ``*_at`` dict builds and ``all(g in …)``
  membership scans).  These carry the ``# hostplane-hot`` marker:
  raftlint's ``host-loop`` rule bans ``for``-over-rows inside them so
  the vectorization cannot rot back into per-row Python.

* scalar twins — ``classify_static_scalar`` / ``build_merge_sets_scalar``
  replicate the pre-vectorization per-row logic verbatim.  They are
  the PARITY ORACLE: with ``PARITY`` enabled (env
  ``DRAGONBOAT_TPU_HOSTPLANE_PARITY=1``, or set directly by tests) the
  colocated engine runs both implementations on every generation and
  fail-stops on any divergence.

The scalar ``_plan_device`` classifier in ops/engine.py remains the
slow-path fallback for rows that fail the static prefilter — exactly
the contract the ``plan_ok`` fast tick lane (57 µs -> 5 µs) proved.
Deliberately numpy-only: nothing here may touch jax — the host plane
must never inject device syncs into the launch tail (that is the
device plane's job, audited separately by analysis/jaxcheck).
"""
from __future__ import annotations

import os
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from .types import (
    F_ANY_LIVE,
    F_APPEND,
    F_COUNT,
    F_ESC,
    F_NEED_SS,
    F_QUORUM_ACTIVE,
    F_QUORUM_FRESH,
    MT_TICK,
    R_COMMIT,
    R_LAST,
    R_LEADER,
    R_ROLE,
    R_TERM,
    R_VOTE,
    ROLE_LEADER,
    U_COMMIT,
    U_LEADER,
    U_LOST_LEAD,
    U_ROLE,
    U_STATE,
    UL_N,
)

# parity mode: run the scalar twins beside every vectorized pass and
# assert identical outputs (tests flip the module attribute directly;
# the env var serves soak/CI runs).  Off by default — the twins are
# O(rows) Python, the very cost this module exists to remove.
PARITY = os.environ.get("DRAGONBOAT_TPU_HOSTPLANE_PARITY", "") == "1"


class HostPlaneParityError(AssertionError):
    """Vectorized and scalar host-plane passes disagreed (a bug in one
    of them); the engine fail-stops the launch loudly rather than
    letting the two decode paths diverge the cluster."""


class RowLanes:
    """SoA metadata lanes for device rows — the ``_RowMeta`` truth store.

    One lane per static plan fact the classifier needs:

    * ``attached`` — a ``_RowMeta`` exists for this row (set at attach,
      cleared at detach/halt/release; ``attached & ~dirty`` is the
      device-authoritative "alive" set the launch masks ride on).
    * ``dirty`` — the scalar Raft is authoritative and the device row
      is stale (fresh rows, cold-stepped rows, escalated rows).
    * ``plan_ok`` — the last FULL ``_plan_device`` pass passed every
      static eligibility check (the fast tick lane's proof).
    * ``esc_hold`` — steps left to hold the row on the scalar path
      after an escalation.

    All writes happen under the engine's core lock (the same contract
    the ``_RowMeta`` fields always had); the vectorized readers run
    under that lock too.
    """

    __slots__ = ("attached", "dirty", "plan_ok", "esc_hold")

    def __init__(self, capacity: int):
        self.attached = np.zeros((capacity,), bool)
        # rows start dirty: scalar-authoritative until the first upload
        self.dirty = np.ones((capacity,), bool)
        self.plan_ok = np.zeros((capacity,), bool)
        self.esc_hold = np.zeros((capacity,), np.int64)

    def reset_row(self, g: int, attached: bool) -> None:
        """Fresh-row state (attach) or freed-row state (detach/halt)."""
        self.attached[g] = attached
        self.dirty[g] = True
        self.plan_ok[g] = False
        self.esc_hold[g] = 0

    def alive_mask(self) -> np.ndarray:  # hostplane-hot
        """The device-authoritative row set: attached and clean.  A
        fresh [G] bool array (callers mutate it for per-generation
        stopping corrections).  Replaces the old per-launch Python scan
        over the whole ``_meta`` table (~0.5 µs/row — ~125 ms/launch at
        250k rows)."""
        return self.attached & ~self.dirty


class LeaseLanes:
    """Host model of resident CheckQuorum leaders' activity windows —
    the WINDOW form of the device-plane lease evidence (ROADMAP 4b),
    kept by ``VectorStepEngine`` alone (its peers answer over a
    transport whose time in flight the device cannot see, so it takes
    evidence once a CheckQuorum window; the colocated engine renews
    every launch, :class:`LeaseAges`; the scalar path anchors every
    response at its probe's send tick: docs/GATEWAY.md "Lease-read
    safety" states the three side by side).

    The device SoA tracks ``check_quorum``/``active`` per row but never
    drove the scalar remotes' ``last_resp_tick``, so lease reads on
    device-hosted shards always fell back to ReadIndex.  The wiring:

    * the kernel's flags word gains ``F_QUORUM_ACTIVE`` — a CheckQuorum
      leader whose CURRENT activity window already holds a quorum of
      active voter lanes (engine._summarize_flags; rides the existing
      per-launch readback for free);
    * the host mirrors each armed row's device ``election_tick`` from
      the ticks it feeds (``row_step``), so it knows when the device's
      CheckQuorum sweep cleared the lanes — the WINDOW START, recorded
      on the row's own node clock;
    * when the flag is up mid-window, the scalar voting remotes are
      anchored at that window start (``Raft.anchor_quorum_evidence``),
      and ``quorum_responded_tick``/``lease_remaining_ticks`` work
      unchanged.

    SAFETY SHAPE: an ``active`` lane proves its peer responded AFTER
    the sweep observed it cleared, so the quorum's election clocks
    reset no earlier than (window start - one in-flight probe delay).
    Window-start anchoring is therefore the classic clock-based
    CheckQuorum lease (etcd's leader lease), one notch weaker than the
    scalar path's probe-send FIFO anchoring; the margin lease callers
    already keep (NodeHost.try_lease_read) absorbs the in-flight skew.
    The leader's own FIRST window is never anchored (window_start
    starts at -1): become_leader fabricates a full activity window
    (kernel._become_leader), and only a window that began with a real
    on-device sweep counts as evidence.

    All writes run under the engine's lock, like RowLanes.
    """

    __slots__ = ("window_start", "dev_el", "et")

    def __init__(self, capacity: int):
        self.window_start = np.full((capacity,), -1, np.int64)
        self.dev_el = np.zeros((capacity,), np.int64)
        self.et = np.zeros((capacity,), np.int64)  # 0 = disarmed

    def disarm(self, g: int) -> None:
        self.et[g] = 0
        self.dev_el[g] = 0
        self.window_start[g] = -1

    def arm(self, g: int, election_timeout: int, election_tick: int) -> None:
        """Arm a row entering device residency (or winning an election
        on-device) as a CheckQuorum leader.  ``election_tick`` seeds
        the device-window mirror (uploads carry the scalar's tick; an
        on-device win resets it to 0)."""
        self.et[g] = election_timeout
        self.dev_el[g] = election_tick
        self.window_start[g] = -1  # first window: fabricated actives

    def row_step(self, g: int, fed_ticks: int, now: int,
                 flags_word: int) -> int:
        """Advance one armed row by the ticks its launch fed and return
        the anchor tick (>= 0) when the quorum-active flag holds inside
        an observed window, else -1.  Crossings mirror kernel._tick's
        leader leg exactly: el += n, fired at el >= et, reset to 0 (the
        planner's half-window tick cap guarantees at most one crossing
        per launch)."""
        et = self.et[g]
        if et <= 0:
            return -1
        el = self.dev_el[g] + fed_ticks
        if el >= et:
            # the device's CheckQuorum sweep ran this launch: actives
            # cleared, a fresh window starts on this row's clock NOW
            self.dev_el[g] = 0
            self.window_start[g] = now
            return -1
        self.dev_el[g] = el
        ws = self.window_start[g]
        if ws >= 0 and (flags_word & F_QUORUM_ACTIVE):
            return int(ws)
        return -1


# "no anchor" in the age lanes: past any election timeout, and far
# enough from the int64 ceiling that adding fed ticks never wraps
LEASE_NONE = 1 << 40
# what a row's clock jumps by when the row stops being the engine's to
# count (LeaseAges.disarm): more than any election timeout, so every
# lease anchored against its earlier clock is over, and 2**40 such
# jumps fit an int64
LEASE_GONE = 1 << 20


class LeaseAges:
    """The colocated engine's lease evidence, renewed EVERY launch and
    kept as lanes: for an armed row (a resident CheckQuorum leader)
    ``age[g]`` is the ticks since its lease anchor ON THE CLOCK OF THE
    VOTER FURTHEST AHEAD — the row's own or a resident peer's,
    whichever the launches have fed more — and ``LEASE_NONE`` while it
    has no anchor.  ``Node.lease_probe`` reads ``election_timeout -
    age[g]`` straight off the lane (one element load on the reader's
    thread, no lock); no scalar remote is touched and Python walks a
    row only where its role changed.

    EVIDENCE.  Bit 1 of the device's ``active`` lane is set by every
    replicate / heartbeat response and cleared for a leader where a
    launch's tick slot is handled (kernel._tick), and
    ``F_QUORUM_FRESH`` is up while a quorum of voter lanes (self
    implicit) carry it: a quorum answered AFTER the row's last tick
    feed.  The tick slot is the last slot of a launch's host region and
    the routed regions come first (colocated._assemble_inbox), so what
    sets the bit in the feeding launch is an answer the later rounds of
    that launch routed back, device to device; an answer handled
    earlier in the same round is lost to the clear, the safe side.
    Every answer that counts was therefore given by a row RESIDENT ON
    THIS ENGINE, whose clock this engine feeds.

    ANCHOR.  The reading of every clock BEFORE that feed's launch:
    the row's own (``since[g]`` is what its clock has added since;
    after the completion's bookkeeping adds the feed's ``t`` ticks the
    anchor is exactly ``t`` old) and each resident peer's
    (``mark[g, p]`` is ``clk`` of the peer's row at that reading).
    They are kept until the next feed: a tick launch with nothing else
    to do is a single round, and the answers to its heartbeat arrive
    in the NEXT launch, which may feed the row no tick — the flag then
    anchors it at the feed it still belongs to (``own = since``,
    ``base = mark``).

    AGE.  ``clk[r]`` counts every tick the engine has added to row
    ``r``'s clock.  After every completion, and whenever a row leaves
    the device, ``age[g] = max(own[g], max_p(clk[peers[g, p]] -
    base[g, p]))`` for every anchored row of the engine, stepped or
    not.  No quorum: it grows with whichever clock runs fastest, and
    the lease is over once ANY voter it may rest on has been fed
    ``election_timeout`` ticks since the anchor.

    SAFETY SHAPE: a response handled after the clock reading that
    becomes the anchor.  The responder reset its election clock when
    it handled the probe it answers, in a round of the feeding launch
    ``N`` or later — at the earliest in ``N``'s first round, BEFORE
    its own tick slot (routed first) — so since its reset its clock
    has been fed only ticks of launches ``>= N``, every one of which
    ``clk`` counts from ``mark`` on, and it refuses every vote until
    that is ``election_timeout`` of them (Raft._in_lease, kernel's
    in_lease).  The lease is measured on the responder's own clock, so
    it needs NO assumption about how evenly rows are stepped or how a
    ticker keeps time: a leader whose row is starved, or whose ticker
    stands still, loses its lease by its peers' clocks.  A completion
    counts a launch's ticks before it hands anything of that launch on
    (updates, messages, commits to apply), completions run in launch
    order, and nothing a client can see of a later term leaves the
    engine but through the completion of the launch that elected its
    leader or a later one: by then this lane says the lease is over.
    A row that leaves the device (evicted to the scalar path,
    released, halted) or changes role is ``disarm``ed, which jumps its
    ``clk`` past any lease: what the scalar path feeds it the engine
    cannot count.  A row whose resident peers change (``set_peers``)
    starts over.  Routed delivery takes exactly one round and is never
    queued.  What is NOT bounded is a response that came through the
    HOST inbox (a peer row on the host path, a route over budget): it
    sat in a transport queue for a time the device cannot see.  It is
    never counted: in a launch that feeds the row a tick every host
    slot precedes the tick slot, so the clear takes it; a row stepped
    with a host message and NO tick has ``since`` set to ``LEASE_NONE``
    until its next feed, and a leader whose peers answer through the
    host reads through ReadIndex.  A row stepped with no tick whose
    host slots held no message (proposals, reads, transfers: nothing
    a response handler sees) keeps ``since``, as a row not stepped
    does: the bit it ends with was set by routed answers alone.
    docs/GATEWAY.md "Lease-read safety" and docs/PARITY.md carry the
    argument in full.

    A FRESH LEADER has no bit 1 (``_become_leader`` fabricates bit 0
    only), so its first anchor is a real quorum of answers.

    THE PROBE'S RACE.  ``arm`` hands the row's node a cell ``(lanes, g,
    token)``; ``disarm`` bumps ``token[g]``, clears ``age[g]`` and
    takes the cell back, and every release of a row disarms it before
    the slot can be attached again (all under the core lock).  The
    probe loads ``age[g]`` FIRST and ``token[g]`` SECOND: a token that
    still matches was not yet bumped when it was loaded, so the age
    loaded before it was written while the row was this node's; any
    age written for a later owner follows a bump, and is refused.

    All writes run under the engine's core lock, like RowLanes.
    """

    __slots__ = ("et", "age", "own", "since", "clk", "peers", "mark",
                 "base", "token", "holder", "node_of")

    def __init__(self, capacity: int, P: int, node_of=None):
        self.et = np.zeros((capacity,), np.int64)  # 0 = disarmed
        # what the probe reads: ticks since the anchor on the clock of
        # the voter furthest ahead
        self.age = np.full((capacity,), LEASE_NONE, np.int64)
        # the row's own clock since its anchor ...
        self.own = np.full((capacity,), LEASE_NONE, np.int64)
        # ... and since the reading BEFORE its last tick feed
        # (LEASE_NONE: no feed that evidence may be anchored at)
        self.since = np.full((capacity,), LEASE_NONE, np.int64)
        # every tick the engine has added to each row's clock; one
        # more slot, never moved, stands for "no such row"
        self.clk = np.zeros((capacity + 1,), np.int64)
        # each row's peers resident on this engine, as rows (capacity:
        # none), whatever a partition cuts: set_peers
        self.peers = np.full((capacity, P), capacity, np.int64)
        # the peers' clk at the reading before the row's last tick
        # feed, and at its anchor
        self.mark = np.zeros((capacity, P), np.int64)
        self.base = np.zeros((capacity, P), np.int64)
        self.token = np.zeros((capacity,), np.int64)
        # the node holding each armed row's cell, and how to find a
        # row's node when it is armed
        self.holder: List = [None] * capacity
        self.node_of = node_of

    def copy(self) -> "LeaseAges":
        """Lanes of their own with the same contents and no readers
        (the parity oracle steps a copy beside the real ones)."""
        other = LeaseAges(0, 0)
        for name in ("et", "age", "own", "since", "clk", "peers", "mark",
                     "base", "token"):
            setattr(other, name, getattr(self, name).copy())
        other.holder = [None] * len(self.holder)
        return other

    def disarm(self, g: int) -> None:
        """Row ``g`` is not, or no longer, a resident leader — and
        whatever it is now, the engine stops vouching for its clock:
        it leaves the device (the scalar path feeds it what the engine
        cannot count), or its role changed.  Its ``clk`` jumps past
        any lease, so every lease that may rest on its answer is over
        NOW."""
        self.token[g] += 1  # before anything a later owner may write
        self.et[g] = 0
        self.age[g] = self.own[g] = self.since[g] = LEASE_NONE
        self.clk[g] += LEASE_GONE
        self._refresh()
        node = self.holder[g]
        if node is not None:
            self.holder[g] = None
            node.lease_cell = None

    def arm(self, g: int, election_timeout: int) -> None:
        """Arm a row entering device residency (or winning an election
        on-device) as a CheckQuorum leader, with no anchor yet, and
        hand its node the cell."""
        self.disarm(g)
        self.et[g] = election_timeout
        if self.node_of is not None:
            node = self.node_of(g)
            self.holder[g] = node
            node.lease_cell = (self, g, int(self.token[g]))

    def set_peers(self, dest: np.ndarray) -> None:
        """``dest[g, p]``: the row of ``g``'s peer ``p`` on this engine,
        -1 where it has none (the route table BEFORE any partition is
        cut into it: a peer that is cut off is fed all the same).  A
        row whose peers changed starts over: its marks were read
        against the rows it had."""
        new = np.where(dest >= 0, dest, len(self.et)).astype(np.int64)
        changed = np.nonzero((new != self.peers).any(axis=1))[0]
        self.peers = new
        self.age[changed] = self.own[changed] = LEASE_NONE
        self.since[changed] = LEASE_NONE

    def _refresh(self) -> None:  # hostplane-hot
        """``age`` of every anchored row from the clocks as they
        stand: its own since the anchor, or a resident peer's if that
        has been fed more."""
        rows = np.nonzero(self.own < LEASE_NONE)[0]
        if len(rows):
            ahead = (self.clk[self.peers[rows]] - self.base[rows]).max(
                axis=1, initial=0
            )
            self.age[rows] = np.minimum(
                np.maximum(self.own[rows], ahead), LEASE_NONE
            )

    def idle(self, gs, clock) -> None:  # hostplane-hot
        """Rows (an index array, or one row id) whose clocks advance by
        ``clock`` ticks OUTSIDE a completion — ticks quiesce swallowed,
        a launch that raised: their leases age with their clocks (and
        those of the leaders they answer to at the next completion: no
        device clock moved)."""
        # (a disarmed row holds LEASE_NONE in all three, and stays there)
        self.age[gs] = np.minimum(self.age[gs] + clock, LEASE_NONE)
        self.own[gs] = np.minimum(self.own[gs] + clock, LEASE_NONE)
        self.since[gs] = np.minimum(self.since[gs] + clock, LEASE_NONE)
        self.clk[gs] += clock

    def lanes_step(  # hostplane-hot
        self, gs: np.ndarray, clock: np.ndarray, fed: np.ndarray,
        flags: np.ndarray, quiet: np.ndarray,
    ) -> Tuple[int, int, int]:
        """One completion's pass: ``gs`` the rows it stepped (distinct),
        ``clock`` the clock ticks each one's bookkeeping is about to
        add, ``fed`` the ticks its device row was fed, ``flags`` the
        final round's ``[G]`` word, ``quiet`` (bool over ``gs``) the
        rows whose host slots carried no message.  Reads the peers'
        clocks for every armed row fed a tick BEFORE any clock of this
        launch moves, moves the clocks, anchors every armed row of the
        engine whose flag is up at the feed it belongs to, and works
        out every anchored row's age afresh.  Returns the armed rows
        stepped with ticks, how many of them were anchored, and how
        many rows of the engine were, at this feed or an earlier one
        (``lease_rows_armed``, ``lease_rows_fresh``,
        ``lease_rows_anchored``).
        :func:`lease_rows_step` is the per-row twin."""
        ticked = (self.et[gs] > 0) & (fed > 0)
        tk = gs[ticked]
        self.mark[tk] = self.clk[self.peers[tk]]
        # (a disarmed row holds LEASE_NONE, and stays there)
        self.own[gs] = np.minimum(self.own[gs] + clock, LEASE_NONE)
        # a row stepped WITHOUT a tick took host slots with no clear
        # behind them: nothing may be anchored until its next feed --
        # unless no slot held a message (proposals, reads: nothing that
        # sets the bit), when it keeps what it had, as an unstepped row
        dry = np.where(quiet & (fed == 0),
                       np.minimum(self.since[gs] + clock, LEASE_NONE),
                       LEASE_NONE)
        self.since[gs] = np.where(ticked, clock, dry)
        self.clk[gs] += clock
        fresh = (flags & F_QUORUM_FRESH) != 0
        hit = np.nonzero(fresh & (self.since < self.own))[0]
        self.own[hit] = self.since[hit]
        self.base[hit] = self.mark[hit]
        self._refresh()
        return (
            int(np.count_nonzero(ticked)),
            int(np.count_nonzero(ticked & fresh[gs])),
            len(hit),
        )


def lease_rows_step(lease: LeaseAges, stepped: Dict[int, Tuple[int, int]],
                    flags, quiet) -> None:
    """Per-row twin of :meth:`LeaseAges.lanes_step`, a row at a time:
    ``stepped`` is row -> (clock ticks, ticks fed) for the rows the
    completion stepped, ``quiet`` the rows of them whose host slots
    carried no message."""
    P = lease.peers.shape[1]
    for g, (clock, fed) in stepped.items():
        if lease.et[g] > 0 and fed > 0:
            for p in range(P):
                lease.mark[g, p] = lease.clk[lease.peers[g, p]]
    for g, (clock, fed) in stepped.items():
        lease.own[g] = min(int(lease.own[g]) + clock, LEASE_NONE)
        if lease.et[g] > 0 and fed > 0:
            lease.since[g] = clock
        elif g in quiet and fed == 0:
            lease.since[g] = min(int(lease.since[g]) + clock, LEASE_NONE)
        else:
            lease.since[g] = LEASE_NONE
        lease.clk[g] += clock
    for g in range(len(lease.et)):
        if int(flags[g]) & F_QUORUM_FRESH and lease.since[g] < lease.own[g]:
            lease.own[g] = lease.since[g]
            lease.base[g] = lease.mark[g]
        if lease.own[g] < LEASE_NONE:
            ahead = max(
                [0] + [int(lease.clk[lease.peers[g, p]] - lease.base[g, p])
                       for p in range(P)]
            )
            lease.age[g] = min(max(int(lease.own[g]), ahead), LEASE_NONE)


def lease_pass_rows(lease: LeaseAges, whole, stepped, flags, vals_np,
                    pos_sum, mirror_role, gone, quiet) -> None:
    """Per-row twin of the colocated completion's lease pass, over
    ``whole`` (one ``(node, g, si)`` a stepped or live row) on a COPY
    of the lanes: a row whose role left or reached leader against
    ``mirror_role`` is disarmed or armed, then :func:`lease_rows_step`
    runs over the rows of ``stepped`` (row -> (clock ticks, ticks
    fed)) that ``whole`` still holds, ``quiet`` as there.
    ``gone(node, g)`` says a row is stopped or detached since the
    launch."""
    kept = {}
    for node, g, _si in whole:
        if gone(node, g):
            continue
        if vals_np is not None and len(vals_np):
            k = int(pos_sum[g])
            if k >= 0:
                role = int(vals_np[k, R_ROLE])
                if role != int(mirror_role[g]):
                    r = node.peer.raft
                    if role == ROLE_LEADER and r.check_quorum:
                        lease.arm(g, r.election_timeout)
                    else:
                        lease.disarm(g)
        if g in stepped:
            kept[g] = stepped[g]
    lease_rows_step(lease, kept, flags, quiet)


class UpdateLanes:
    """SoA mirror of the scalar words the merge tail syncs into each
    resident row's ``Raft`` — the array-side ``pb.Update`` truth store
    (ISSUE 13 / ROADMAP item 1's "Raft-less host rows").

    One ``[UL_N, G]`` int64 block, rows indexed by the values-block
    layout (``types.R_TERM`` … ``types.R_LAST``), holding the LAST
    SYNCED absolute-frame words per device row: term / vote / commit /
    leader / role / last-log-index (commit and last carry the shard
    base added back, so rebases never perturb them).  Beside the lanes
    the device plane already tracks per row — delivered outbox bits
    (the head blob), lease evidence (:class:`LeaseLanes`) and the
    plan/alive flags (:class:`RowLanes`) — this completes the set: a
    generation's *effects* now diff as ``new words != lane words``
    over whole ``[G]`` gathers (:func:`plan_update_sync`) instead of
    one Python object walk per affected row.

    Chip-sharded by construction: the block's G axis is the engine row
    axis, so under the ``ops/placement.py`` row-block contract a
    device's G-slice is the contiguous column slice
    :meth:`device_slice` returns — per-device lane views compose with
    zero copies (docs/MULTICHIP.md), ready for the mesh plane.

    Lifecycle mirrors the ``_mirror`` table: seeded at upload
    (``_upload_rows``) from the scalar raft, bulk-written at every
    merge for the rows the generation synced; rows skipped by a merge
    (stopped / halted mid-flight) are freed and re-seeded at their
    next upload, so their stale words are moot.  All access runs under
    the engine's core lock, like RowLanes.
    """

    __slots__ = ("words",)

    def __init__(self, capacity: int):
        self.words = np.zeros((UL_N, capacity), np.int64)

    def seed_row(self, g: int, term: int, vote: int, commit: int,
                 leader: int, role: int, last: int) -> None:
        """Scalar -> lanes at upload: the raft is authoritative."""
        w = self.words
        w[R_TERM, g] = term
        w[R_VOTE, g] = vote
        w[R_COMMIT, g] = commit
        w[R_LEADER, g] = leader
        w[R_ROLE, g] = role
        w[R_LAST, g] = last

    def device_slice(self, device_index: int, n_devices: int) -> np.ndarray:
        """The contiguous per-device lane view under the row-block
        contract (placement.device_of_row): device ``d`` owns columns
        ``[d*Gl, (d+1)*Gl)``.  A VIEW, never a copy — the mesh test
        asserts the slices tile the block exactly."""
        from .placement import rows_per_device

        per = rows_per_device(self.words.shape[1], n_devices)
        return self.words[:, device_index * per:(device_index + 1) * per]


class UpdateSyncPlan(NamedTuple):
    """One generation's vectorized effect classification: the new
    absolute words ``[UL_N, n]`` for the planned rows and the per-row
    ``U_*`` effect bits ``[n]`` (0 = the row's merged values are
    byte-identical to the last sync — nothing to write, persist or
    notify)."""

    words: np.ndarray
    ubits: np.ndarray


def plan_update_sync(  # hostplane-hot
    old_words: np.ndarray,
    sum_k: np.ndarray,
    vals: np.ndarray,
    bases: np.ndarray,
) -> UpdateSyncPlan:
    """Vectorized update-sync classification for one generation.

    ``old_words`` is the ``[UL_N, n]`` gather of the rows' current
    lanes, ``sum_k`` the per-row position into the ``[m, N_VALS]``
    values block (-1 = the row carried no values this generation —
    its words are kept and its ubits are 0), ``bases`` the per-row
    shard bases converting the device frame to the absolute frame.

    The ``U_*`` bits come from lane diffs, NOT from the device's
    F_CHANGED flag: F_CHANGED compares one step's old/new device
    state, while the lanes compare against the last HOST sync — the
    quantity the merge tail actually owes an action for.  The caller
    writes ``plan.words`` back into the lanes for exactly the rows it
    then merges (skipped rows re-seed at their next upload).
    """
    in_sum = sum_k >= 0
    if not len(vals):
        # no row carried values this generation: every sum_k is -1 and
        # the gather below must still be indexable
        vals = np.zeros((1, UL_N), np.int64)
    safe_k = np.where(in_sum, sum_k, 0)
    new = vals[safe_k, :UL_N].T.astype(np.int64)
    new[R_COMMIT] += bases
    new[R_LAST] += bases
    new = np.where(in_sum[None, :], new, old_words)
    state_chg = (
        (new[R_TERM] != old_words[R_TERM])
        | (new[R_VOTE] != old_words[R_VOTE])
        | (new[R_COMMIT] != old_words[R_COMMIT])
    )
    ubits = (
        np.where(state_chg, U_STATE, 0)
        | np.where(new[R_COMMIT] > old_words[R_COMMIT], U_COMMIT, 0)
        | np.where(new[R_ROLE] != old_words[R_ROLE], U_ROLE, 0)
        | np.where(new[R_LEADER] != old_words[R_LEADER], U_LEADER, 0)
        | np.where(
            (old_words[R_ROLE] == ROLE_LEADER)
            & (new[R_ROLE] != ROLE_LEADER),
            U_LOST_LEAD,
            0,
        )
    )
    return UpdateSyncPlan(words=new, ubits=ubits)


# raftlint: ignore[host-loop] parity oracle — the per-row decision shape the lanes replaced, kept for the harness
def plan_update_sync_scalar(  # hostplane-hot
    old_words: np.ndarray,
    sum_k: Sequence[int],
    vals: np.ndarray,
    bases: Sequence[int],
) -> UpdateSyncPlan:
    """Per-row twin of :func:`plan_update_sync` — the old merge loop's
    implicit per-row comparisons (scalar sync always wrote, commit
    advance probed ``committed > r.log.committed``, role/leader
    transitions probed per row), made explicit row by row."""
    n = len(sum_k)
    words = np.array(old_words, np.int64, copy=True)
    ubits = np.zeros((n,), np.int64)
    for i in range(n):
        k = int(sum_k[i])
        if k < 0:
            continue
        term, vote, commit, leader, role, last = (
            int(vals[k, c]) for c in range(UL_N)
        )
        commit += int(bases[i])
        last += int(bases[i])
        ub = 0
        if (
            term != int(old_words[R_TERM, i])
            or vote != int(old_words[R_VOTE, i])
            or commit != int(old_words[R_COMMIT, i])
        ):
            ub |= U_STATE
        if commit > int(old_words[R_COMMIT, i]):
            ub |= U_COMMIT
        if role != int(old_words[R_ROLE, i]):
            ub |= U_ROLE
        if leader != int(old_words[R_LEADER, i]):
            ub |= U_LEADER
        if (
            int(old_words[R_ROLE, i]) == ROLE_LEADER
            and role != ROLE_LEADER
        ):
            ub |= U_LOST_LEAD
        words[:, i] = (term, vote, commit, leader, role, last)
        ubits[i] = ub
    return UpdateSyncPlan(words=words, ubits=ubits)


def assert_update_plan_parity(
    old_words: np.ndarray,
    sum_k: np.ndarray,
    vals: np.ndarray,
    bases: np.ndarray,
    plan: UpdateSyncPlan,
) -> None:
    ref = plan_update_sync_scalar(
        old_words, np.asarray(sum_k).tolist(), vals,
        np.asarray(bases).tolist(),
    )
    if not np.array_equal(np.asarray(plan.ubits), ref.ubits):
        raise HostPlaneParityError(_diff("update_ubits", plan.ubits,
                                         ref.ubits))
    if not np.array_equal(np.asarray(plan.words), ref.words):
        raise HostPlaneParityError(_diff("update_words", plan.words,
                                         ref.words))


def check_update_plan_parity(old_words, sum_k, vals, bases, plan) -> None:
    try:
        assert_update_plan_parity(old_words, sum_k, vals, bases, plan)
    except HostPlaneParityError as e:  # pragma: no cover - bug path
        _record_failure(e)


# ---------------------------------------------------------------------------
# the batched plan classifier (static-eligibility prefilter)
# ---------------------------------------------------------------------------


def classify_static(lanes: RowLanes, gs: np.ndarray) -> np.ndarray:  # hostplane-hot
    """[n] bool: rows whose last full-plan proof still stands.

    ``gs`` is the per-node row-id array (-1 for unattached).  A True
    lane means the row may take the fast tick lane PROVIDED the cheap
    per-launch dynamic conditions (empty queues, no snapshot/read
    state, save quarantine, stale binding) also hold — those live on
    Python objects and are re-verified per row by the caller, exactly
    as the fast lane always did.  A False lane routes the node to the
    scalar ``_plan_device`` classifier (the slow-path oracle)."""
    ok = gs >= 0
    safe = np.where(ok, gs, 0)
    return (
        ok
        & lanes.plan_ok[safe]
        & ~lanes.dirty[safe]
        & (lanes.esc_hold[safe] == 0)
    )


# raftlint: ignore[host-loop] parity oracle — the pre-vectorization per-row shape, kept for the harness
def classify_static_scalar(lanes: RowLanes, gs: Sequence[int]) -> np.ndarray:
    """Per-row twin of :func:`classify_static` (the r5 probe shape)."""
    out = np.zeros((len(gs),), bool)
    for i, g in enumerate(gs):
        if g < 0:
            continue
        out[i] = (
            bool(lanes.plan_ok[g])
            and not bool(lanes.dirty[g])
            and int(lanes.esc_hold[g]) == 0
        )
    return out


# ---------------------------------------------------------------------------
# the tick lane (the launch's encode phase)
# ---------------------------------------------------------------------------
_NO_ROWS = np.zeros((0,), np.int64)


class TickLane:
    """One launch's tick-only rows as parallel columns, from the plan
    loop to the end of the completion: a row on the lane is never a
    Python tuple, a ``StepInputs`` or a plan list.

    The plan loop appends to the four lists (``gs`` row id, ``fed`` the
    fused tick count the device is fed, ``ticks`` the ticks drained —
    more than ``fed`` where quiesce swallowed some — and ``nodes``);
    ``gc`` holds, by row id, the ticks the backlog cap dropped, for the
    few rows that have any.  :meth:`seal` (at the launch, after
    ``_retake_lane_rows`` gave rows back) freezes the columns the
    completion's array passes read: ``gs_np``, ``fed_np`` and
    ``clock_np`` = ticks + dropped ticks, what both clocks advance by.
    A slow path that needs one row's inputs back (an escalation's
    replay) builds a ``StepInputs(ticks, gc_ticks)`` for that row from
    ``ticks``/``gc`` (docs/PARITY.md "The tick lane through
    completion")."""

    __slots__ = ("gs", "fed", "ticks", "nodes", "gc", "gs_np", "fed_np",
                 "clock_np")

    def __init__(self):
        self.gs: List[int] = []
        self.fed: List[int] = []
        self.ticks: List[int] = []
        self.nodes: list = []
        self.gc: Dict[int, int] = {}
        self.gs_np = self.fed_np = self.clock_np = _NO_ROWS

    def __len__(self) -> int:
        return len(self.gs)

    def add(self, node, g: int, fed: int, ticks: int, gc: int = 0) -> None:
        """One row (the plan loop open-codes this with bound appends)."""
        self.gs.append(g)
        self.fed.append(fed)
        self.ticks.append(ticks)
        self.nodes.append(node)
        if gc:
            self.gc[g] = gc

    def take(self, g: int) -> Tuple:
        """Remove row ``g`` and return ``(node, fed, ticks, gc)``: the
        row turned out to carry more than a hint-free tick."""
        i = self.gs.index(g)
        del self.gs[i]
        return (self.nodes.pop(i), self.fed.pop(i), self.ticks.pop(i),
                self.gc.pop(g, 0))

    def seal(self) -> "TickLane":  # hostplane-hot
        self.gs_np = np.asarray(self.gs, np.int64)
        self.fed_np = np.asarray(self.fed, np.int64)
        clock = np.asarray(self.ticks, np.int64)
        if self.gc:
            # the rows the backlog cap dropped ticks of: none in a healthy
            # launch, every row of the lane once a launch outlasts the
            # election window (found on the chip, PR 32: a list.index a
            # row made this pass quadratic, 170 ms at 5,250 rows, and
            # kept the launches that long)
            at = dict(zip(self.gs, range(len(self.gs))))
            # raftlint: ignore[host-loop] one dict probe a capped row
            for g, n in self.gc.items():
                clock[at[g]] += n
        self.clock_np = clock
        return self


class LaunchEncode(NamedTuple):
    """One generation's host-side encode, as ``_launch_generation``
    consumes it: the ``[G]`` fused tick count of every row whose whole
    host inbox is one hint-free tick, the rows that upload dense inbox
    rows (``(g, Message list)``), the staged payload entries by row and
    assembled slot, the proposal-slot rows, and the ACTIVE rows' row ->
    fused tick count map (the lane's counts stay in ``TickLane.fed``)."""

    tick_counts: np.ndarray
    sparse: List[Tuple[int, list]]
    staging: Dict[int, Dict[int, list]]
    prop_rows: List[int]
    tick_fed: Dict[int, int]


def encode_tick_lane(  # hostplane-hot
    G: int, tick_gs: Sequence[int], tick_n: Sequence[int]
) -> np.ndarray:
    """The tick lane's whole encode.  ``tick_gs``/``tick_n`` are the
    row ids and fused tick counts of the rows the plan loop's fast lane
    put on the :class:`TickLane` (sole plan ``[("tick", n)]``, no
    pending device-read ctx, so the tick would carry no hint): ~98 % of
    a launch at 1,000 groups x 3.  One numpy store puts them into the
    ``[G]`` count vector ``colocated._host_inbox`` expands on the
    device.  No ``Message``, no dict, no per-row Python: the per-row
    twin (:func:`split_lone_ticks` over ``_encode_rows``' output) is
    what every batch row took before, still takes when it carries
    anything else, and is the parity oracle for these."""
    tick_counts = np.zeros((G,), np.int32)
    if len(tick_gs):
        tick_counts[np.asarray(tick_gs, np.int64)] = np.asarray(
            tick_n, np.int32
        )
    return tick_counts


def split_lone_ticks(
    tick_counts: np.ndarray, rows, row_msgs_of
) -> List[Tuple[int, list]]:
    """Per-row twin of :func:`encode_tick_lane`: of ``rows`` (batch
    tuples) and their ``Message`` lists (``_encode_rows``' first
    return, parallel to ``rows``), a row whose inbox is one hint-free
    tick lands in ``tick_counts`` (written in place); every other row
    with input is returned as ``(g, msgs)`` for the dense upload.  Runs
    over the launch's ACTIVE rows, and over the whole batch as the
    parity oracle."""
    sparse: List[Tuple[int, list]] = []
    for (_node, g, _si, _plan), msgs in zip(rows, row_msgs_of):
        if not msgs:
            continue
        m0 = msgs[0]
        if (
            len(msgs) == 1
            and int(m0.type) == MT_TICK
            and m0.hint == 0
            and m0.hint_high == 0
        ):
            tick_counts[g] = m0.log_index
        else:
            sparse.append((g, msgs))
    return sparse


def assert_encode_parity(
    batch, batch_gs: np.ndarray, lane: LaunchEncode, ref: LaunchEncode,
    tick_lane: "TickLane" = None,
) -> None:
    """``lane`` (tick lane + active rows) against ``ref`` (the whole
    batch through ``_encode_rows`` + :func:`split_lone_ticks`): same
    count vector, same dense rows with equal ``Message`` lists, same
    staging, proposal rows and lease-pass tick map (the active rows'
    ``tick_fed`` and ``tick_lane``'s counts together); ``batch`` is the
    whole stepped set as tuples, active rows first, and ``batch_gs``
    its row ids in that order."""
    want_gs = [g for _, g, _, _ in batch]
    if np.asarray(batch_gs).tolist() != want_gs:
        raise HostPlaneParityError(_diff("batch_gs", batch_gs, want_gs))
    if not np.array_equal(lane.tick_counts, ref.tick_counts):
        raise HostPlaneParityError(
            _diff("tick_counts", lane.tick_counts, ref.tick_counts)
        )
    got, want = dict(lane.sparse), dict(ref.sparse)
    if len(got) != len(lane.sparse) or got != want:
        raise HostPlaneParityError(
            f"sparse rows: lane {sorted(got)} != whole batch "
            f"{sorted(want)} (or a row's Message list differs: "
            f"{[g for g in got if g in want and got[g] != want[g]][:8]})"
        )
    if lane.staging != ref.staging:
        raise HostPlaneParityError(
            _diff("staging rows", sorted(lane.staging), sorted(ref.staging))
        )
    if sorted(lane.prop_rows) != sorted(ref.prop_rows):
        raise HostPlaneParityError(
            _diff("prop_rows", sorted(lane.prop_rows), sorted(ref.prop_rows))
        )
    fed = dict(lane.tick_fed)
    if tick_lane is not None:
        fed.update(zip(tick_lane.gs, tick_lane.fed))
    if fed != ref.tick_fed:
        raise HostPlaneParityError(
            _diff("tick_fed", sorted(fed.items()),
                  sorted(ref.tick_fed.items()))
        )


# ---------------------------------------------------------------------------
# merge row sets (the post-launch tail's classification)
# ---------------------------------------------------------------------------
class MergeSets(NamedTuple):
    """Row sets the merge stage consumes, as sorted int32 row-id arrays
    (``esc_batch_pos`` is positions into the BATCH list, everything
    else is device row ids).  Replaces the old per-row list/dict
    comprehensions over the whole meta table."""

    esc_batch_pos: np.ndarray  # batch positions whose row escalated
    esc_other: np.ndarray      # alive non-batch rows that escalated
    live_other: np.ndarray     # alive non-batch rows with any-live flags
    buf_rows: np.ndarray       # live rows with host-visible outbox bytes
    append_rows: np.ndarray    # live rows that ring-appended
    slot_rows: np.ndarray      # non-escalated proposal-slot rows
    need_rows: np.ndarray      # live rows with a peer needing a snapshot
    sum_rows: np.ndarray       # live rows whose VALUES the merge reads


def _mask_of(G: int, rows) -> np.ndarray:  # hostplane-hot
    m = np.zeros((G,), bool)
    if len(rows):
        m[np.asarray(rows, np.int64)] = True
    return m


def build_merge_sets(  # hostplane-hot
    flags: np.ndarray,
    alive: np.ndarray,
    batch_gs: np.ndarray,
    prop_gs: np.ndarray,
    *,
    G: int,
) -> MergeSets:
    """Vectorized merge-row classification for one launch.

    Inputs: the [G] int32 flags word (types.F_*), the [G] bool alive
    mask (attached & clean, with this generation's stopping rows
    cleared), the batch row ids in batch order, and the proposal-slot
    row ids.  Mirrors the scalar semantics bit for bit (the parity
    harness holds both to it):

    * escalated batch rows replay on the scalar path; escalated ALIVE
      non-batch rows (stepped only by routed traffic) just discard
      their device effects;
    * live = batch rows + alive resident rows with any-live flags,
      minus escalations;
    * buf/append/need sets are flag-gated subsets of live; slot rows
      are the non-escalated proposal rows; sum rows are live rows with
      any-live flags or proposal slots (the rest only ticked).
    """
    batch_mask = _mask_of(G, batch_gs)
    prop_mask = _mask_of(G, prop_gs)
    esc = (flags & F_ESC) != 0
    anylive = (flags & F_ANY_LIVE) != 0
    esc_batch_pos = np.nonzero(esc[batch_gs])[0].astype(np.int32) if len(
        batch_gs
    ) else np.zeros((0,), np.int32)
    esc_other = np.nonzero(alive & ~batch_mask & esc)[0].astype(np.int32)
    live_mask = ~esc & (batch_mask | (alive & ~batch_mask & anylive))
    slot_mask = prop_mask & ~esc  # prop rows ride the batch; esc drops them
    i32 = np.int32
    return MergeSets(
        esc_batch_pos=esc_batch_pos,
        esc_other=esc_other,
        live_other=np.nonzero(live_mask & ~batch_mask)[0].astype(i32),
        buf_rows=np.nonzero(live_mask & ((flags & F_COUNT) != 0))[0].astype(i32),
        append_rows=np.nonzero(live_mask & ((flags & F_APPEND) != 0))[0].astype(i32),
        slot_rows=np.nonzero(slot_mask)[0].astype(i32),
        need_rows=np.nonzero(live_mask & ((flags & F_NEED_SS) != 0))[0].astype(i32),
        sum_rows=np.nonzero(live_mask & (anylive | slot_mask))[0].astype(i32),
    )


# raftlint: ignore[host-loop] parity oracle — replicates the r5 per-row loops verbatim for the harness
def build_merge_sets_scalar(
    flags: Sequence[int],
    alive: Sequence[bool],
    batch_gs: Sequence[int],
    prop_gs: Sequence[int],
    *,
    G: int,
) -> MergeSets:
    """Per-row twin of :func:`build_merge_sets` — the exact loop shapes
    the colocated merge tail ran before vectorization (flag probes per
    row, membership via Python sets), with outputs sorted into the
    canonical MergeSets form for comparison."""
    flags = list(flags)
    batch_set = set(int(g) for g in batch_gs)
    esc_batch_pos = [
        i for i, g in enumerate(batch_gs) if flags[int(g)] & F_ESC
    ]
    esc_other = [
        g for g in range(G)
        if alive[g] and g not in batch_set and flags[g] & F_ESC
    ]
    esc_set = {int(batch_gs[i]) for i in esc_batch_pos} | set(esc_other)
    live = [int(g) for g in batch_gs if int(g) not in esc_set]
    for g in range(G):
        if (
            alive[g]
            and g not in batch_set
            and g not in esc_set
            and flags[g] & F_ANY_LIVE
        ):
            live.append(g)
    slot_rows = [int(g) for g in prop_gs if int(g) not in esc_set]
    slot_set = set(slot_rows)
    buf_rows = [g for g in live if flags[g] & F_COUNT]
    append_rows = [g for g in live if flags[g] & F_APPEND]
    need_rows = [g for g in live if flags[g] & F_NEED_SS]
    sum_rows = [
        g for g in live if (flags[g] & F_ANY_LIVE) or g in slot_set
    ]
    live_other = [g for g in live if g not in batch_set]
    srt = lambda xs: np.asarray(sorted(xs), np.int32)  # noqa: E731
    return MergeSets(
        esc_batch_pos=np.asarray(sorted(esc_batch_pos), np.int32),
        esc_other=srt(esc_other),
        live_other=srt(live_other),
        buf_rows=srt(buf_rows),
        append_rows=srt(append_rows),
        slot_rows=srt(slot_rows),
        need_rows=srt(need_rows),
        sum_rows=srt(sum_rows),
    )


# ---------------------------------------------------------------------------
# index maps (the *_at dict replacements)
# ---------------------------------------------------------------------------
def pos_of(G: int, rows: np.ndarray) -> np.ndarray:  # hostplane-hot
    """[G] int32 position map: pos[g] = index of g in ``rows``, -1
    elsewhere — the index-array replacement for the per-row
    ``{g: k for k, g in enumerate(rows)}`` dict builds."""
    pos = np.full((G,), -1, np.int32)
    n = len(rows)
    if n:
        pos[np.asarray(rows, np.int64)] = np.arange(n, dtype=np.int32)
    return pos


def covered(pos: np.ndarray, rows: np.ndarray) -> bool:  # hostplane-hot
    """Every row of ``rows`` has a position in ``pos`` — the
    index-array replacement for ``all(g in at for g in rows)``."""
    if not len(rows):
        return True
    return bool((pos[np.asarray(rows, np.int64)] >= 0).all())


# ---------------------------------------------------------------------------
# parity harness
# ---------------------------------------------------------------------------
def _diff(name: str, a: np.ndarray, b: np.ndarray) -> str:
    return (
        f"{name}: vectorized {np.asarray(a).tolist()[:32]} != "
        f"scalar {np.asarray(b).tolist()[:32]}"
    )


def assert_classify_parity(lanes: RowLanes, gs: Sequence[int],
                           vec: np.ndarray) -> None:
    ref = classify_static_scalar(lanes, list(gs))
    if not np.array_equal(np.asarray(vec, bool), ref):
        raise HostPlaneParityError(_diff("classify_static", vec, ref))


def assert_merge_parity(
    flags: np.ndarray,
    alive: np.ndarray,
    batch_gs: np.ndarray,
    prop_gs: np.ndarray,
    vec: MergeSets,
    *,
    G: int,
) -> None:
    """Run the scalar oracle on the same launch inputs and compare
    every set (vectorized outputs sorted first — the oracle's canonical
    form).  Raises :class:`HostPlaneParityError` naming the first
    diverging set."""
    ref = build_merge_sets_scalar(
        np.asarray(flags).tolist(),
        np.asarray(alive, bool).tolist(),
        list(np.asarray(batch_gs).tolist()),
        list(np.asarray(prop_gs).tolist()),
        G=G,
    )
    for name in MergeSets._fields:
        got = np.sort(np.asarray(getattr(vec, name)))
        want = np.asarray(getattr(ref, name))
        if not np.array_equal(got, want):
            raise HostPlaneParityError(_diff(name, got, want))


# parity failures observed by the in-engine checker (check_* wrappers):
# the engine must not crash a live launch mid-merge over a checker
# finding, so the wrappers record + log instead of raising — tests and
# soaks gate on PARITY_FAILURE_COUNT == 0 / the list being empty.  The
# list keeps only the first _FAILURE_CAP diffs (a multi-day soak with
# a persistent divergence appends per launch — an unbounded list would
# OOM the soak long before anyone reads it); the counter is exact.
PARITY_FAILURES: List[str] = []
PARITY_FAILURE_COUNT = 0
_FAILURE_CAP = 256


def _record_failure(e: Exception) -> None:  # pragma: no cover - bug path
    global PARITY_FAILURE_COUNT
    PARITY_FAILURE_COUNT += 1
    if len(PARITY_FAILURES) < _FAILURE_CAP:
        PARITY_FAILURES.append(str(e))


def check_classify_parity(lanes: RowLanes, gs, vec) -> None:
    try:
        assert_classify_parity(lanes, gs, vec)
    except HostPlaneParityError as e:  # pragma: no cover - bug path
        _record_failure(e)


def check_encode_parity(batch, batch_gs, lane, ref, tick_lane=None) -> None:
    try:
        assert_encode_parity(batch, batch_gs, lane, ref, tick_lane)
    except HostPlaneParityError as e:  # pragma: no cover - bug path
        _record_failure(e)


def check_upload_parity(flat: np.ndarray, ref: np.ndarray) -> None:
    """The launch's one upload (``colocated._pack_launch``) packed from
    the lane encode against the same packed from the whole-batch
    encode: combo, position map and dense rows, int for int."""
    if not np.array_equal(flat, ref):  # pragma: no cover - bug path
        _record_failure(HostPlaneParityError(
            _diff("launch upload", flat, ref)
        ))


class CompletionTrace(NamedTuple):
    """What one completion's live build, lease pass and tick
    bookkeeping came to, in a form both ways of running them can fill
    in: the array passes over the stepped rows (PR 29) and the per-row
    passes over one tuple a stepped row (the parity oracle).

    ``emitted`` is the set of rows the completion can emit anything
    for (a live row with values, or one an earlier round touched: the
    array side walks no others); ``rows`` the rows the completion
    stepped or found live; ``et``, ``age``, ``own``, ``since`` and
    ``clk`` the lease lanes (:class:`LeaseAges`) over every row of the
    engine after the pass; ``clocks`` row -> (node clock, raft clock)
    after the bookkeeping."""

    emitted: frozenset = frozenset()
    rows: np.ndarray = _NO_ROWS
    et: np.ndarray = _NO_ROWS
    age: np.ndarray = _NO_ROWS
    own: np.ndarray = _NO_ROWS
    since: np.ndarray = _NO_ROWS
    clk: np.ndarray = _NO_ROWS
    clocks: Dict[int, Tuple[int, int]] = {}


def assert_completion_parity(got: CompletionTrace,
                             want: CompletionTrace) -> None:
    """The array passes' trace against the per-row passes': the same
    rows emitted, the same lease lanes and clocks.  Raises
    :class:`HostPlaneParityError` naming the first that differs."""
    if got.emitted != want.emitted:
        raise HostPlaneParityError(
            _diff("rows emitted", sorted(got.emitted), sorted(want.emitted))
        )
    if not np.array_equal(got.rows, want.rows):
        raise HostPlaneParityError(_diff("lease rows", got.rows, want.rows))
    for name in ("et", "age", "own", "since", "clk"):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        if not np.array_equal(a, b):
            bad = np.nonzero(a != b)[0][:8]
            raise HostPlaneParityError(
                f"lease {name}: rows {bad.tolist()} array "
                f"{a[bad].tolist()} != per-row {b[bad].tolist()}"
            )
    a, b = got.clocks, want.clocks
    if a != b:
        bad = sorted(g for g in set(a) | set(b) if a.get(g) != b.get(g))[:8]
        raise HostPlaneParityError(
            f"clocks: rows {bad} array {[a.get(g) for g in bad]} "
            f"!= per-row {[b.get(g) for g in bad]}"
        )


def check_completion_parity(got: CompletionTrace,
                            want: CompletionTrace) -> None:
    try:
        assert_completion_parity(got, want)
    except HostPlaneParityError as e:  # pragma: no cover - bug path
        _record_failure(e)


def check_merge_parity(flags, alive, batch_gs, prop_gs, vec, *, G) -> None:
    try:
        assert_merge_parity(flags, alive, batch_gs, prop_gs, vec, G=G)
    except HostPlaneParityError as e:  # pragma: no cover - bug path
        _record_failure(e)


# recorded generation traces (parity satellite): with ``RECORD`` on,
# the colocated engine appends one entry per launch so tests can replay
# scalar-vs-vectorized over REAL generation inputs (elections,
# escalations, membership churn) rather than only fabricated ones.
RECORD = False
TRACE: List[dict] = []
_TRACE_CAP = 512


def record_generation(flags, alive, batch_gs, prop_gs, G: int) -> None:
    if not RECORD:
        return
    TRACE.append(
        dict(
            flags=np.array(flags, np.int64, copy=True),
            alive=np.array(alive, bool, copy=True),
            batch_gs=np.array(batch_gs, np.int64, copy=True),
            prop_gs=np.array(prop_gs, np.int64, copy=True),
            G=G,
        )
    )
    if len(TRACE) > _TRACE_CAP:
        del TRACE[: len(TRACE) - _TRACE_CAP]
