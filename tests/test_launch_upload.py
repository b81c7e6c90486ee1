"""The launch's inputs as one upload and one program (PR 36).

``colocated._pack_launch`` packs the ``[G, 4]`` combo, the dense rows'
position map and their inboxes into ONE flat int32 vector, and
``colocated._host_inbox`` unpacks it on the device into ``(combo, host
inbox)``.  They replaced a transfer a field and two programs
(``_host_inbox_from_ticks``, ``_scatter_inbox_rows``), kept below as
the reference:

  (a) for seeded random launches the new program's host inbox equals
      the old pair's, field by field, and the combo comes back as sent;
  (b) a quiet launch hands the runtime exactly 1 array, and 10 programs
      on a K = 3 wave, 4 on a single round, counted by shims over
      ``jax.device_put`` and the registry's programs, and the engine's
      ``device_puts`` / ``device_programs`` agree with the shims;
  (d) after ``_warm()`` no bucket of dense rows traces anything new.

(c), a fused wave against K serial launches, is tests/test_pipeline.py's
and tests/test_fused_wave*.py's, untouched.
"""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu.analysis import jitcheck
from dragonboat_tpu.ops import colocated as C
from dragonboat_tpu.ops import engine, hostplane
from dragonboat_tpu.ops import kernel as K
from dragonboat_tpu.ops import registry
from dragonboat_tpu.ops import sync as S
from dragonboat_tpu.ops.engine import _bucket, _place_rows, _pos_map
from dragonboat_tpu.ops.types import I32, MT_TICK, Inbox
from dragonboat_tpu.pb import Entry, EntryType, Message, MessageType

G, M, E = 64, 8, 4


# -- the reference: the encode and the two programs as they were --------
def old_encode_inbox(batches, M, E):
    n = len(batches)
    cols = {k: np.zeros((n, M), np.int32) for k in S.INBOX_FIELDS}
    ent_term = np.zeros((n, M, E), np.int32)
    ent_cc = np.zeros((n, M, E), np.int32)
    overflow = []
    for g, msgs in enumerate(batches):
        if len(msgs) > M:
            overflow.append(g)
            continue
        for i, m in enumerate(msgs):
            if len(m.entries) > E:
                overflow.append(g)
                break
            cols["mtype"][g, i] = int(m.type)
            cols["from_id"][g, i] = m.from_
            cols["term"][g, i] = m.term
            cols["log_term"][g, i] = m.log_term
            cols["log_index"][g, i] = m.log_index
            cols["commit"][g, i] = m.commit
            cols["reject"][g, i] = int(m.reject)
            cols["hint"][g, i] = m.hint
            cols["hint_high"][g, i] = m.hint_high
            cols["n_entries"][g, i] = len(m.entries)
            for j, e in enumerate(m.entries):
                ent_term[g, i, j] = e.term
                ent_cc[g, i, j] = int(e.is_config_change())
    return Inbox(**cols, ent_term=ent_term, ent_cc=ent_cc), overflow


def old_host_inbox_from_ticks(combo, M, E):
    tick_counts = combo[:, C._C_TICKS]
    n = tick_counts.shape[0]
    z = jnp.zeros((n, M), I32)
    ze = jnp.zeros((n, M, E), I32)
    has = tick_counts > 0
    return Inbox(
        mtype=z.at[:, 0].set(jnp.where(has, MT_TICK, 0)),
        from_id=z, term=z, log_term=z,
        log_index=z.at[:, 0].set(tick_counts),
        commit=z, reject=z, hint=z, hint_high=z, n_entries=z,
        ent_term=ze, ent_cc=ze,
    )


def old_scatter_inbox_rows(host, pos, sub):
    return Inbox(*(
        _place_rows(getattr(host, f), getattr(sub, f), pos)
        for f in Inbox._fields
    ))


def old_launch_inbox(tick_counts, alive, batch_gs, prop_gs, sparse):
    """The host inbox as ``_launch_generation`` built it before PR 36:
    dense rows padded to the bucket with copies of the last."""
    combo = jnp.asarray(C._combo_np(tick_counts, alive, batch_gs, prop_gs))
    host = old_host_inbox_from_ticks(combo, M, E)
    if sparse:
        nsb = _bucket(len(sparse))
        batches = ([m for _, m in sparse]
                   + [sparse[-1][1]] * (nsb - len(sparse)))
        sub, overflow = old_encode_inbox(batches, M, E)
        assert not overflow
        host = old_scatter_inbox_rows(
            host, jnp.asarray(_pos_map(G, [g for g, _ in sparse])),
            Inbox(*map(jnp.asarray, sub)),
        )
    return combo, host


# -- seeded launches ----------------------------------------------------
def _msg(rng, n_entries=None):
    n = rng.randrange(E + 1) if n_entries is None else n_entries
    return Message(
        type=rng.choice([MessageType.PROPOSE, MessageType.REPLICATE,
                         MessageType.HEARTBEAT, MessageType.READ_INDEX,
                         MessageType.LOCAL_TICK]),
        from_=rng.randrange(1, 8), term=rng.randrange(1, 2**31 - 1),
        log_term=rng.randrange(2**20), log_index=rng.randrange(2**31 - 1),
        commit=rng.randrange(2**31 - 1), reject=rng.random() < 0.3,
        hint=rng.randrange(2**31 - 1), hint_high=rng.randrange(2**31 - 1),
        entries=tuple(
            Entry(term=rng.randrange(1, 2**20), index=k + 1,
                  type=rng.choice([EntryType.APPLICATION,
                                   EntryType.CONFIG_CHANGE]))
            for k in range(n)
        ),
    )


def _launch(seed, n_dense, n_ticks, full_entries=False, full_slots=False):
    """One launch's host inputs: ``n_dense`` rows with real host slots,
    ``n_ticks`` others with a lone tick, the rest silent."""
    rng = random.Random(seed)
    rows = rng.sample(range(G), n_dense + n_ticks)
    dense, ticks = rows[:n_dense], rows[n_dense:]
    tick_counts = np.zeros((G,), np.int32)
    tick_counts[ticks] = [rng.randrange(1, 9) for _ in ticks]
    sparse = [
        (g, [_msg(rng, E if full_entries else None)
             for _ in range(M if full_slots else rng.randrange(1, M + 1))])
        for g in dense
    ]
    alive = np.asarray([rng.random() < 0.9 for _ in range(G)])
    batch_gs = np.asarray(rows, np.int64)
    prop_gs = np.asarray(
        [g for g in dense if rng.random() < 0.5], np.int64)
    return tick_counts, alive, batch_gs, prop_gs, sparse


CASES = {
    "no_dense_row": dict(n_dense=0, n_ticks=40),
    "nothing_at_all": dict(n_dense=0, n_ticks=0),
    "one_dense_row": dict(n_dense=1, n_ticks=20),
    "a_full_bucket": dict(n_dense=8, n_ticks=20),
    "one_past_a_bucket": dict(n_dense=9, n_ticks=20),
    "one_short_of_a_bucket": dict(n_dense=15, n_ticks=5),
    "every_row_dense": dict(n_dense=G, n_ticks=0),
    "rows_with_E_entries": dict(n_dense=5, n_ticks=10, full_entries=True),
    "rows_with_M_slots_of_E_entries": dict(
        n_dense=3, n_ticks=0, full_entries=True, full_slots=True),
    "lone_ticks_beside_dense_rows": dict(n_dense=6, n_ticks=58),
}


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
@pytest.mark.parametrize("case", sorted(CASES))
def test_one_program_builds_the_inbox_the_two_built(case, seed):
    inputs = _launch(seed, **CASES[case])
    want_combo, want = old_launch_inbox(*inputs)
    flat, nsb = C._pack_launch(G, M, E, *inputs)
    n = len(inputs[-1])
    assert nsb == (_bucket(n) if n else 0)
    assert flat.dtype == np.int32
    assert flat.shape == (5 * G + nsb * S.inbox_row_ints(M, E),)
    combo, got = C._host_inbox(jnp.asarray(flat), G=G, M=M, E=E, NSB=nsb)
    assert np.array_equal(combo, want_combo)
    for f in Inbox._fields:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert a.shape == b.shape and a.dtype == b.dtype, f
        assert np.array_equal(a, b), f


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_packed_encode_is_the_field_encode_and_keeps_its_overflow(seed):
    rng = random.Random(seed)
    batches = [[_msg(rng) for _ in range(rng.randrange(M + 1))]
               for _ in range(12)]
    # a row over M slots is left empty; a message over E entries stops
    # its row where it stands: both are named, neither raises
    batches[3] = [_msg(rng) for _ in range(M + 1)]
    batches[7] = [_msg(rng, 1), _msg(rng, E + 1), _msg(rng, 1)]
    want, want_over = old_encode_inbox(batches, M, E)
    block, over = S.encode_inbox_np(batches, M, E)
    assert over == want_over == [3, 7]
    assert block.shape == (12, S.inbox_row_ints(M, E))
    got = S.unpack_inbox(block, M, E)
    dev, dev_over = S.encode_inbox(batches, M, E)
    assert dev_over == want_over
    for f in Inbox._fields:
        assert np.array_equal(getattr(got, f), getattr(want, f)), f
        assert np.array_equal(np.asarray(getattr(dev, f)),
                              getattr(want, f)), f
        assert isinstance(getattr(dev, f), jax.Array)


@pytest.mark.parametrize("value", [2**31, -2**31 - 1])
@pytest.mark.parametrize("field", ["from_", "term", "log_term", "log_index",
                                   "commit", "hint", "hint_high",
                                   "entry_term"])
def test_a_field_outside_int32_is_refused_not_wrapped(field, value):
    # the device reads int32 lanes: a value that does not fit must stop
    # the encode, as a store of one element does, and never wrap
    rng = random.Random(13)
    base = _msg(rng, 2)
    rows = [[_msg(rng)], [_msg(rng)]]

    def batches(v):
        if field == "entry_term":
            entry = Entry(term=v, index=2, type=EntryType.APPLICATION)
            m = dataclasses.replace(base, entries=(base.entries[0], entry))
        else:
            m = dataclasses.replace(base, **{field: v})
        return [rows[0], rows[1] + [m]]

    with pytest.raises(OverflowError):
        old_encode_inbox(batches(value), M, E)
    with pytest.raises(OverflowError):
        S.encode_inbox_np(batches(value), M, E)
    # the largest and the smallest that fit pass, bit for bit
    for edge in (2**31 - 1, -2**31):
        want, _ = old_encode_inbox(batches(edge), M, E)
        got = S.unpack_inbox(S.encode_inbox_np(batches(edge), M, E)[0], M, E)
        for f in Inbox._fields:
            assert np.array_equal(getattr(got, f), getattr(want, f)), f


def test_a_planner_that_let_an_oversized_row_through_is_named():
    rng = random.Random(9)
    inputs = list(_launch(9, n_dense=2, n_ticks=0))
    g = inputs[-1][1][0]
    inputs[-1][1] = (g, [_msg(rng) for _ in range(M + 1)])
    with pytest.raises(AssertionError, match=rf"\[{g}\]"):
        C._pack_launch(G, M, E, *inputs)


def test_the_upload_oracle_records_a_packed_form_that_differs():
    flat, _ = C._pack_launch(G, M, E, *_launch(11, n_dense=3, n_ticks=4))
    before = hostplane.PARITY_FAILURE_COUNT
    hostplane.check_upload_parity(flat, flat.copy())
    assert hostplane.PARITY_FAILURE_COUNT == before
    other = flat.copy()
    other[5 * G + 2] += 1
    try:
        hostplane.check_upload_parity(flat, other)
        assert hostplane.PARITY_FAILURE_COUNT == before + 1
        assert "launch upload" in hostplane.PARITY_FAILURES[-1]
    finally:
        # the failure was made here: leave the oracle as it was found
        hostplane.PARITY_FAILURE_COUNT = before
        hostplane.PARITY_FAILURES.pop()


# -- the engine: what a launch calls, and that nothing retraces ---------
def test_no_bucket_of_dense_rows_retraces_after_warm():
    geom = dict(capacity=16, P=3, W=16, M=M, E=E, O=16, budget=4)
    core = C.ColocatedVectorEngine(**geom)
    sentry = jitcheck.Sentry()
    sentry.mark()
    cap, R = geom["capacity"], S.inbox_row_ints(M, E)
    for nsb in [0] + [1 << k for k in range(cap.bit_length())]:
        flat = np.zeros((5 * cap + nsb * R,), np.int32)
        combo, host = C._host_inbox(
            core._put(flat), G=cap, M=M, E=E, NSB=nsb)
        C._assemble_and_step(core._state, host, core._pending, combo,
                             out_capacity=geom["O"])
        # rounds 2..K of a wave: the resident empty inbox, never donated
        C._assemble_and_step(core._state, core._zero_host, core._pending,
                             combo, out_capacity=geom["O"])
    assert sentry.retraces() == []
    assert not any(np.asarray(f).any() for f in core._zero_host)


def _count_real_calls(monkeypatch):
    """Counting shims over ``jax.device_put`` and over every jitted
    program of the registry, in the modules that call them: what the
    engine really hands the runtime, whatever its own counters say."""
    real = {"device_puts": 0, "device_programs": 0}
    device_put = jax.device_put

    def counted_put(*args, **kwargs):
        real["device_puts"] += 1
        return device_put(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counted_put)
    for name, fn in registry.runtime_entry_points():
        def counted_program(*args, _fn=fn, **kwargs):
            real["device_programs"] += 1
            return _fn(*args, **kwargs)

        attr = name.split(".", 1)[1]
        callers = [m for m in (C, engine, K) if getattr(m, attr, None) is fn]
        assert callers or name.startswith("route."), name
        for mod in callers:
            monkeypatch.setattr(mod, attr, counted_program)
    return real


def test_a_quiet_launch_is_one_upload_and_ten_programs_or_four(monkeypatch):
    from test_nodehost import KVStore, propose_r, set_cmd, wait_for_leader
    from test_pipeline import close_all, make_cluster, read_r

    group, nhs = make_cluster(KVStore, "upload", fused_rounds=3)
    try:
        lead = wait_for_leader(nhs)
        core = group.core
        nh = nhs[lead]
        sess = nh.get_noop_session(1)
        propose_r(nh, sess, set_cmd("warm", b"1"))
        seen = []
        launch = core._launch_generation
        quiet = ("uploaded_rows", "sel_fallbacks", "host_rows_stepped",
                 "escalations", "pipeline_fences")
        calls = ("device_puts", "device_programs")

        def counted(batch, lane):
            st, was = dict(core.stats), dict(real)
            dirty = core._tables_dirty
            launch(batch, lane)
            now = core.stats
            if dirty or any(now.get(k, 0) != st.get(k, 0) for k in quiet):
                return  # a row moved, or a fallback: not a quiet launch
            seen.append((
                now["fused_waves"] - st["fused_waves"],
                *(real[k] - was[k] for k in calls),
            ))

        with core._lock:
            real = _count_real_calls(monkeypatch)
            st0 = dict(core.stats)
            core._launch_generation = counted
        for i in range(12):
            propose_r(nh, sess, set_cmd(f"k{i}", b"1"))
        # a follower's read takes its row to the host and back: the
        # eviction's gathers and the upload are the same thread's calls
        follower = next(r for r in nhs if r != lead)
        assert read_r(nhs[follower], 1, "k0") == b"1"
        for i in range(4):
            propose_r(nh, sess, set_cmd(f"m{i}", b"1"))
        with core._lock:
            core._launch_generation = launch
            st = dict(core.stats)
            monkeypatch.undo()
        # counted by the shims, not by the engine: the runtime was handed
        # exactly one array and ten programs (four on a single round)
        kinds = set(seen)
        assert kinds == {(1, 1, 10), (0, 1, 4)}, kinds
        # and over everything the engine did meanwhile, quiet or not, its
        # counters are what the shims saw
        assert st["evict_host_plan"] > st0.get("evict_host_plan", 0)
        assert st["uploaded_rows"] > st0["uploaded_rows"]
        for k in calls:
            assert st[k] - st0[k] == real[k] > 0, (k, st[k] - st0[k], real)
        assert st["divergence_halts"] == 0 and st["pipeline_resets"] == 0
    finally:
        close_all(nhs)
