"""Registry of every jitted device-plane entry point in ``ops/``.

The device plane's load-bearing contracts — pure int32 math (kernel.py),
no host round-trips inside compiled programs, G-last internal layout,
real buffer donation — existed only as docstrings until this registry:
``analysis/jaxcheck.py`` walks it, traces each entry point with the
canonical small geometry below, and machine-checks the jaxprs and
lowerings against policy (docs/ANALYSIS.md "Device-plane audit").  The
runtime half (``analysis/jitcheck.py``) snapshots each entry's jit
trace-cache size after engine warmup and reports post-warmup retraces.

Keeping the registry IN ops/ (next to the entry points) is deliberate:
adding a ``@jax.jit`` here without registering it fails the auditor's
``unregistered-jit`` rule, so the list cannot silently rot.

Canonical geometry: every dimension is given a DISTINCT size so the
auditor can identify axes by size alone (the G-last rule finds the G
axis as "the axis of size CANON['G']"); G is the only size that may
appear in a batched array, so keep the others unique and small.

Scope note (r6): ``ops/hostplane.py`` — the array-at-once host-plane
machinery — is deliberately numpy-only and carries NO jitted entry
points, so it registers nothing here; the auditor's
``unregistered-jit`` AST scan covers it like every other ops/ module,
and any future ``@jax.jit`` added there must be registered or the
scan fails.  Its per-row discipline is enforced separately by
raftlint's ``host-loop`` rule (docs/ANALYSIS.md).

Scope note (r9): the update-lane plane (``hostplane.UpdateLanes`` /
``plan_update_sync``, ``ops/engine._plan_lane_words``, the batched
persist paths in both merge tails — ISSUE 13) is host-side numpy over
the ALREADY-read-back values blob: no new device programs, no new
jitted entry points, nothing to register.  The same ``unregistered-
jit`` scan and the ``host-loop`` rule (now spanning ``ops/engine.py``)
gate it.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import colocated as C
from . import engine as E
from . import kernel as K
from . import route as R
from . import sync as S
from .types import I32, make_inbox, make_out, make_state

# canonical audit geometry — sizes chosen pairwise-distinct (see module
# docstring); PB = P*budget is the colocated routed-region width and
# M_ASM = M + PB the assembled inbox width
CANON = dict(G=64, P=3, W=8, M=5, E=2, O=8, budget=2)
CANON["PB"] = CANON["P"] * CANON["budget"]
CANON["M_ASM"] = CANON["M"] + CANON["PB"]


class EntryPoint(NamedTuple):
    """One audited jitted callable.

    ``build`` returns ``(args, static_kwargs)`` at the canonical
    geometry; ``donate`` mirrors the jit declaration's donate_argnums
    (the donation audit recomputes the expected alias count from the
    built args); ``g_last`` opts into the internal-layout rule (only
    sound for programs whose WHOLE body runs G-trailing); ``runtime``
    marks entries the recompile sentry watches (audit-only wrappers,
    which production never calls, are excluded so their cold caches
    don't read as permanent warmup)."""

    name: str
    fn: Callable
    build: Callable[[], Tuple[tuple, dict]]
    donate: Tuple[int, ...] = ()
    g_last: bool = False
    runtime: bool = True


def _g():
    return CANON["G"]


def _state(rows: Optional[int] = None):
    return make_state(rows or _g(), CANON["P"], CANON["W"])


def _inbox(M: int):
    return make_inbox(_g(), M, CANON["E"])


def _out(M: int):
    return make_out(_g(), CANON["P"], M, CANON["E"], CANON["O"])


def _combo():
    return jnp.zeros((_g(), 4), I32)


def _idx(n: int):
    return jnp.zeros((n,), I32)


def _idx4(b: int):
    return jnp.zeros((4, b), I32)


# -- per-entry builders ------------------------------------------------
def _b_step():
    return (_state(), _inbox(CANON["M"])), dict(out_capacity=CANON["O"])


def _b_step_internal():
    st = K.state_to_internal(_state())
    ib = K._inbox_to_internal(_inbox(CANON["M"]))
    return (st, ib), dict(out_capacity=CANON["O"])


def _b_scatter_rows():
    pos = jnp.full((_g(),), -1, I32)
    return (_state(), pos, _state(4)), {}


def _b_select_rows():
    return (jnp.zeros((_g(),), bool), _state(), _state()), {}


def _b_gather_rows():
    return (_state(), _idx(4)), {}


def _b_summarize_flags():
    return (_state(), _state(), _out(CANON["M"])), {}


def _b_gather_vals():
    return (_state(), _out(CANON["M"]), _idx(4)), {}


def _b_gather_detail():
    return (_state(), _out(CANON["M"]), _idx4(4)), {}


def _b_gather_detail_vals():
    return (_state(), _out(CANON["M"]), _idx4(4), _idx(4)), {}


def _b_set_remote_snapshot():
    return (_state(), _idx(1), _idx(1), _idx(1)), {}


def _b_assemble_inbox():
    return (
        _inbox(CANON["M"]),
        _inbox(CANON["PB"]),
        jnp.ones((_g(),), bool),
    ), {}


def _b_assemble_and_step():
    return (
        _state(), _inbox(CANON["M"]), _inbox(CANON["PB"]), _combo(),
    ), dict(out_capacity=CANON["O"])


def _b_route_step():
    dest = jnp.full((_g(), CANON["P"]), -1, I32)
    rank = jnp.zeros((_g(), CANON["P"]), I32)
    return (
        _state(), _state(), _out(CANON["M_ASM"]), dest, rank, _combo(),
    ), dict(PB=CANON["PB"], E=CANON["E"], budget=CANON["budget"])


def _b_select_and_blob():
    G = _g()
    nwords = (CANON["O"] + 31) // 32
    return (
        _state(),
        _out(CANON["M_ASM"]),
        jnp.zeros((6,), I32),
        jnp.zeros((G, nwords), jnp.uint32),
        jnp.zeros((G,), I32),
        _combo(),
    ), dict(
        CAP_B=16, CAP_SL=G, CAP_N=8, CAP_A=G, CAP_S=G,
        HOST_OFF=CANON["PB"],
    )


def _b_zero_inbox_rows():
    return (_inbox(CANON["M_ASM"]), jnp.zeros((_g(),), bool)), {}


def _b_host_inbox():
    G, M, E, NSB = _g(), CANON["M"], CANON["E"], 4
    flat = jnp.zeros((5 * G + NSB * S.inbox_row_ints(M, E),), I32)
    return (flat,), dict(G=G, M=M, E=E, NSB=NSB)


# audit-only jit of the consensus round: route() itself is a pure
# function callers jit; this wrapper puts its program under the same
# dtype/transfer audit as everything else
_routed_round_audit = functools.partial(
    jax.jit,
    static_argnames=(
        "out_capacity", "budget", "base", "propose_leaders", "propose_n",
    ),
)(R.routed_round)

# audit-only jit of the fused commit wave (ISSUE 15): K routed rounds
# chained inside one program.  rounds=2 at the canonical geometry keeps
# the trace cheap while exercising the round-to-round chaining (the
# dtype/transfer findings of any K>1 are identical — the body is K
# copies of the same round program).
_fused_rounds_audit = functools.partial(
    jax.jit,
    static_argnames=(
        "rounds", "out_capacity", "budget", "base", "propose_leaders",
        "propose_n",
    ),
)(R.fused_rounds)

# routed_round inbox width must satisfy base + P*budget == M
_M_ROUTE = CANON["M_ASM"]
_BASE_ROUTE = _M_ROUTE - CANON["PB"]


def _b_routed_round():
    dest = jnp.full((_g(), CANON["P"]), -1, I32)
    rank = jnp.zeros((_g(), CANON["P"]), I32)
    return (
        _state(), _inbox(_M_ROUTE), dest, rank,
    ), dict(
        out_capacity=CANON["O"], budget=CANON["budget"],
        base=_BASE_ROUTE, propose_leaders=True,
    )


def _b_fused_rounds():
    dest = jnp.full((_g(), CANON["P"]), -1, I32)
    rank = jnp.zeros((_g(), CANON["P"]), I32)
    return (
        _state(), _inbox(_M_ROUTE), dest, rank,
    ), dict(
        rounds=2, out_capacity=CANON["O"], budget=CANON["budget"],
        base=_BASE_ROUTE, propose_leaders=True,
    )


ENTRY_POINTS: Tuple[EntryPoint, ...] = (
    # kernel
    EntryPoint("kernel.step", K.step, _b_step),
    EntryPoint(
        "kernel.step_internal", K.step_internal, _b_step_internal,
        g_last=True,
    ),
    # engine helpers (the per-launch gather/scatter plumbing)
    EntryPoint("engine._scatter_rows", E._scatter_rows, _b_scatter_rows),
    EntryPoint("engine._select_rows", E._select_rows, _b_select_rows),
    EntryPoint("engine._gather_rows", E._gather_rows, _b_gather_rows),
    EntryPoint(
        "engine._summarize_flags", E._summarize_flags, _b_summarize_flags
    ),
    EntryPoint("engine._gather_vals", E._gather_vals, _b_gather_vals),
    EntryPoint("engine._gather_detail", E._gather_detail, _b_gather_detail),
    EntryPoint(
        "engine._gather_detail_vals",
        E._gather_detail_vals,
        _b_gather_detail_vals,
    ),
    EntryPoint(
        "engine._set_remote_snapshot",
        E._set_remote_snapshot,
        _b_set_remote_snapshot,
    ),
    # colocated launch pipeline
    EntryPoint(
        "colocated._assemble_inbox", C._assemble_inbox, _b_assemble_inbox
    ),
    EntryPoint(
        "colocated._assemble_and_step",
        C._assemble_and_step,
        _b_assemble_and_step,
        donate=(2,),
    ),
    EntryPoint(
        "colocated._route_step", C._route_step, _b_route_step, donate=(1,)
    ),
    EntryPoint(
        "colocated._select_and_blob", C._select_and_blob, _b_select_and_blob
    ),
    EntryPoint(
        "colocated._zero_inbox_rows", C._zero_inbox_rows, _b_zero_inbox_rows
    ),
    EntryPoint("colocated._host_inbox", C._host_inbox, _b_host_inbox),
    # route (audit-only jit wrappers)
    EntryPoint(
        "route.routed_round", _routed_round_audit, _b_routed_round,
        runtime=False,
    ),
    EntryPoint(
        "route.fused_rounds", _fused_rounds_audit, _b_fused_rounds,
        runtime=False,
    ),
)


def runtime_entry_points():
    """(name, jitted fn) pairs the recompile sentry watches."""
    return [(ep.name, ep.fn) for ep in ENTRY_POINTS if ep.runtime]


def mesh_entry_points(mesh) -> Tuple[EntryPoint, ...]:
    """Audit entries for the SHARDED launch path over ``mesh`` — the
    jaxcheck transfer/dtype rules extended to the multi-chip programs
    (docs/MULTICHIP.md; the ISSUE-12 "zero cross-device host hops"
    gate).  Not part of the static ENTRY_POINTS tuple because a mesh
    needs visible devices: tests/test_multichip.py audits these
    explicitly under forced host devices.  CANON['G'] must divide the mesh (64 covers 1-8)."""
    import numpy as np

    G = CANON["G"]
    if G % mesh.size:
        raise ValueError(f"CANON G={G} must divide mesh size {mesh.size}")

    step_sharded = K.make_step_sharded(mesh, out_capacity=CANON["O"])
    round_sharded = R.make_sharded_round(
        mesh, M=_M_ROUTE, E=CANON["E"], out_capacity=CANON["O"],
        budget=CANON["budget"], xbudget=4, base=_BASE_ROUTE,
        propose_leaders=True,
    )

    def _b_step_sharded():
        return (_state(), _inbox(CANON["M"])), {}

    def _b_round_sharded():
        # strided tables so every device has genuine cross-device edges
        # in the traced program (an all-local trace would never reach
        # the collective lane)
        dl = jnp.asarray(
            np.zeros((G, CANON["P"]), np.int32)
        )
        dd = jnp.asarray(
            (np.arange(G)[:, None] % mesh.size * np.ones(
                (1, CANON["P"]), np.int64
            )).astype(np.int32)
        )
        rank = jnp.zeros((G, CANON["P"]), I32)
        return (_state(), _inbox(_M_ROUTE), dl, dd, rank), {}

    return (
        EntryPoint(
            "kernel.step_sharded", step_sharded, _b_step_sharded,
            runtime=False,
        ),
        EntryPoint(
            "route.sharded_round", round_sharded, _b_round_sharded,
            runtime=False,
        ),
    )
