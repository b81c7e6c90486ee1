"""The packed forms (PR 38): the state, the inboxes and the step's
outputs cross every jitted program of the engine as a few stacked int32
blocks (types.PackedState / PackedInbox / PackedOut), not one array a
field.  The NamedTuples stay the reference:

  (a) pack then unpack is the identity on a state, an inbox and a
      stepped DeviceOut at P = 3, 5 and 7, and the host-built (numpy)
      constructors are the packed device ones;
  (b) the row helpers over the packed blocks (_scatter_rows,
      _select_rows, _gather_rows, _zero_inbox_rows) do what they did on
      the NamedTuples, row for row;
  (c) a colocated engine's three round programs, recorded over live
      traffic on a K = 1 launch loop and on K = 3 waves with proposals
      and routed traffic in flight, give bit-identical merged state,
      regions, head and detail blobs to the NamedTuple bodies run on the
      same inputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu.ops import colocated as C
from dragonboat_tpu.ops import engine as EN
from dragonboat_tpu.ops import kernel as K
from dragonboat_tpu.ops.types import (
    MT_ELECTION,
    make_inbox,
    make_inbox_packed,
    make_state,
    make_state_packed,
    pack_inbox,
    pack_out,
    pack_state,
    unpack_inbox,
    unpack_out,
    unpack_state,
)

G, W, M, E, O = 12, 8, 6, 3, 16


def _random(nt, seed):
    """``nt`` with every field replaced by seeded int32 noise."""
    rng = np.random.default_rng(seed)
    return type(nt)(*(
        rng.integers(-2**31, 2**31, np.shape(a), dtype=np.int64)
        .astype(np.int32) for a in nt
    ))


def _stepped_out(P):
    """A DeviceOut the kernel produced: every row of P-peer groups told
    to campaign, so the outboxes carry vote requests."""
    n = G // P
    rows = np.arange(n * P)
    peer_ids = np.zeros((G, P), np.int32)
    peer_ids[rows] = np.arange(1, P + 1)[None, :]
    replica_ids = np.zeros((G,), np.int32)
    replica_ids[rows] = rows % P + 1
    st = make_state(G, P, W, shard_ids=np.arange(G) // P,
                    replica_ids=replica_ids, peer_ids=peer_ids)
    ib = make_inbox(G, M, E)
    ib = ib._replace(mtype=ib.mtype.at[rows, 0].set(MT_ELECTION))
    _, out = K.step(st, ib, out_capacity=O)
    assert int(np.asarray(out.count).sum()) > 0
    return out


def _fields_equal(a, b):
    assert type(a) is type(b) or a._fields == b._fields
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.shape == y.shape and x.dtype == y.dtype, f
        assert np.array_equal(x, y), f


def _case(kind, P):
    if kind == "state":
        nt = _random(make_state(G, P, W), P)
        return nt, pack_state, unpack_state
    if kind == "inbox":
        return _random(make_inbox(G, M, E), P), pack_inbox, unpack_inbox
    return _stepped_out(P), pack_out, unpack_out


@pytest.mark.parametrize("P", [3, 5, 7])
@pytest.mark.parametrize("kind", ["state", "inbox", "out"])
def test_pack_then_unpack_is_the_identity(kind, P):
    nt, pack, unpack = _case(kind, P)
    packed = pack(nt)
    assert all(a.dtype == jnp.int32 for a in packed)
    assert len(packed) <= len(nt) / 2
    _fields_equal(unpack(packed), nt)
    # the host (numpy) path packs the same blocks
    host = pack(jax.tree.map(np.asarray, nt), np)
    assert all(isinstance(a, np.ndarray) for a in host)
    for a, b in zip(host, packed):
        assert np.array_equal(a, np.asarray(b))
    # a block keeps G where the field had it: the row axis of a stacked
    # block is its second
    for f in nt._fields:
        assert np.shape(getattr(unpack(packed), f)) == np.shape(
            getattr(nt, f))


@pytest.mark.parametrize("P", [3, 5, 7])
@pytest.mark.parametrize("kind", ["state", "inbox"])
def test_the_host_constructors_are_the_packed_device_ones(kind, P):
    if kind == "state":
        want = pack_state(make_state(G, P, W, replica_ids=np.zeros(G)))
        got = make_state_packed(G, P, W, replica_ids=np.zeros(G))
    else:
        want = pack_inbox(make_inbox(G, P * 2, E))
        got = make_inbox_packed(G, P * 2, E)
    for a, b in zip(got, want):
        assert isinstance(a, np.ndarray) and a.dtype == np.int32
        assert np.array_equal(a, np.asarray(b))


# -- (b) the row helpers, packed against the NamedTuple rows -----------
def _row_sel(mask, a):
    return mask.reshape((-1,) + (1,) * (a.ndim - 1))


def _ref_helper(name, st, other, mask, pos, idx):
    if name == "scatter":
        return jax.tree.map(lambda a, b: np.where(
            _row_sel(pos >= 0, a), b[np.clip(pos, 0, len(b) - 1)], a),
            st, other)
    if name == "select":
        return jax.tree.map(
            lambda a, b: np.where(_row_sel(mask, a), b, a), st, other)
    if name == "gather":
        return jax.tree.map(lambda a: a[idx], st)
    return jax.tree.map(lambda a: np.where(_row_sel(mask, a), 0, a), st)


@pytest.mark.parametrize("name", ["scatter", "select", "gather", "zero"])
def test_the_row_helpers_move_the_rows_they_moved(name):
    rng = np.random.default_rng(7)
    mask = rng.random(G) < 0.5
    idx = rng.integers(0, G, 5).astype(np.int32)
    if name == "zero":
        st = jax.tree.map(np.asarray, _random(make_inbox(G, M, E), 1))
        got = unpack_inbox(C._zero_inbox_rows(pack_inbox(st), mask))
        _fields_equal(got, _ref_helper(name, st, None, mask, None, idx))
        return
    st = jax.tree.map(np.asarray, _random(make_state(G, 5, W), 1))
    if name == "scatter":
        sub = jax.tree.map(np.asarray, _random(make_state(4, 5, W), 2))
        pos = np.full((G,), -1, np.int32)
        pos[[1, 4, 9]] = [3, 0, 2]
        got = EN._scatter_rows(pack_state(st), pos, pack_state(sub))
        want = _ref_helper(name, st, sub, mask, pos, idx)
    elif name == "select":
        new = jax.tree.map(np.asarray, _random(make_state(G, 5, W), 3))
        got = EN._select_rows(mask, pack_state(st), pack_state(new))
        want = _ref_helper(name, st, new, mask, None, idx)
    else:
        got = EN._gather_rows(pack_state(st), idx)
        want = _ref_helper(name, st, None, mask, None, idx)
    _fields_equal(unpack_state(got), want)


# -- (c) the engine's round programs over live traffic -----------------
_ref_step = jax.jit(
    lambda st, host, pending, combo, *, out_capacity: K.step(
        st, C._assemble_inbox(host, pending, combo[:, C._C_ALIVE] != 0),
        out_capacity=out_capacity),
    static_argnames=("out_capacity",))
_ref_route = jax.jit(C._route_step_body,
                     static_argnames=("PB", "E", "budget"))
_ref_blob = jax.jit(C._select_and_blob_body, static_argnames=(
    "CAP_B", "CAP_SL", "CAP_N", "CAP_A", "CAP_S", "HOST_OFF"))


def _record(core, log):
    """Wrap ``core._run``: the three round programs' inputs (read before
    the call: the pending inbox is donated) and outputs, on the host."""
    run = core._run
    watched = {C._assemble_and_step: "step", C._route_step: "route",
               C._select_and_blob: "blob"}

    def recording(prog, *args, **kwargs):
        kind = watched.get(prog)
        if kind is None:
            return run(prog, *args, **kwargs)
        ins = jax.tree.map(np.asarray, args)
        out = run(prog, *args, **kwargs)
        log.append((kind, ins, kwargs, jax.tree.map(np.asarray, out)))
        return out

    return recording


def _check(kind, ins, kw, got):
    if kind == "step":
        st, host, pending, combo = ins
        new, out = _ref_step(unpack_state(st), unpack_inbox(host),
                             unpack_inbox(pending), combo, **kw)
        _fields_equal(unpack_state(got[0]), new)
        _fields_equal(unpack_out(got[1]), out)
        return
    if kind == "route":
        old, new, out, dest, rank, combo = ins
        want = _ref_route(unpack_state(old), unpack_state(new),
                          unpack_out(out), dest, rank, combo, **kw)
        _fields_equal(unpack_state(got[0]), want[0])
        _fields_equal(unpack_inbox(got[1]), want[1])
        got, want = got[2:], want[2:]
    else:
        merged, out, *rest = ins
        want = _ref_blob(unpack_state(merged), unpack_out(out), *rest, **kw)
    for a, b in zip(got, want):
        assert np.array_equal(a, np.asarray(b))


@pytest.mark.parametrize("rounds", [1, 3])
def test_the_round_programs_give_what_the_namedtuple_bodies_give(rounds):
    from test_nodehost import propose_r, set_cmd, wait_for_leader
    from test_nodehost import KVStore
    from test_pipeline import close_all, make_cluster

    group, nhs = make_cluster(KVStore, f"packed{rounds}",
                              fused_rounds=rounds)
    try:
        lead = wait_for_leader(nhs)
        core = group.core
        nh = nhs[lead]
        sess = nh.get_noop_session(1)
        propose_r(nh, sess, set_cmd("warm", b"1"))
        log = []
        with core._lock:
            st0 = dict(core.stats)
            core._run = _record(core, log)
        try:
            for i in range(6):
                propose_r(nh, sess, set_cmd(f"k{i}", b"v"))
        finally:
            with core._lock:
                del core._run
                st1 = dict(core.stats)
        waves = st1["fused_waves"] - st0["fused_waves"]
        assert (waves > 0) == (rounds > 1), waves
        kinds = [k for k, *_ in log]
        assert {"step", "route", "blob"} <= set(kinds)
        # proposals rode a step, and routed traffic was in flight into one
        assert any(ins[3][:, C._C_PROP].any()
                   for k, ins, _, _ in log if k == "step")
        assert any(unpack_inbox(ins[2]).mtype.any()
                   for k, ins, _, _ in log if k == "step")
        for rec in log:
            _check(*rec)
    finally:
        close_all(nhs)
