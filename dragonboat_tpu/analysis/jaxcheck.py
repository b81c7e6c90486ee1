"""jaxcheck: static auditor for the compiled device-plane programs.

raftlint checks what the PYTHON says; jaxcheck checks what the DEVICE
will actually run.  It walks ``ops/registry.py`` (every jitted entry
point in ``ops/``), traces each with the canonical small geometry, and
checks the resulting jaxprs/lowerings against the device-plane policy
that ROADMAP items 1-3 keep piling more logic onto:

``dtype``
    Every intermediate of every program stays in the sanctioned set
    {int32, uint32, bool} (ops/types.py: "all protocol scalars are
    int32" — TPUs have no native int64, and a silent int64/float
    promotion doubles lane traffic or detours through the scalar
    unit).  Entry-point OUTPUTS additionally must not be weak-typed:
    a weak output fed back as the next launch's input re-traces the
    program (the drift the runtime sentry would catch late and this
    catches at lint time).

``transfer``
    No host-transfer primitives (``io_callback`` / ``pure_callback`` /
    ``debug_callback``, infeed/outfeed) inside a compiled hot program:
    every device->host sync stalls the launch for a host round trip —
    one stray ``jax.debug.print`` in the step would erase the
    single-sync launch work.

``donation``
    Every ``donate_argnums`` declaration that CAN alias (a donated
    input whose shape+dtype matches an output) actually does alias in
    the lowering (``tf.aliasing_output``).  A donated-but-unaliased
    buffer where aliasing was possible is the fallback-copy regression
    class of ops/route.py's "aliased zeros break donate_argnums" —
    donation silently degrades to copy + free and the heap grows back
    (the r5 RESOURCE_EXHAUSTED mid-election class).  Declarations with
    NO shape-matched output (e.g. ``_assemble_and_step``'s inboxes,
    donated for early-free) are legitimate and not flagged.

``g-last``
    Internal-layout programs (``kernel.step_internal``) keep G as the
    trailing axis of every computed intermediate, so int32 operands
    pack the 128-wide TPU lane dimension instead of padding it 16-42x
    (ops/kernel.py module docstring).  The G axis is identified by its
    canonical size (registry.CANON — all sizes pairwise distinct);
    constant fills (all-literal inputs, e.g. the make_out constructors
    that fold under jit) are exempt.

``unregistered-jit``
    Every ``@jax.jit``-decorated function in ``ops/*.py`` must appear
    in the registry — the audit cannot cover what it cannot see.

Findings flow through the same baseline ratchet as raftlint
(``analysis/jax_baseline.txt``; gate = zero findings beyond baseline)
via ``python -m dragonboat_tpu.analysis --jax`` (scripts/lint.sh).
The dynamic half — post-warmup retrace detection — is
``analysis/jitcheck.py``.
"""
from __future__ import annotations

import ast
import os
import sys
import warnings
from collections import Counter
from typing import Dict, Iterable, List, Optional, Tuple

from .raftlint import Finding, gate, load_baseline, write_baseline

# dtypes a device-plane intermediate may legally carry (ops/types.py
# int32 policy; uint32 for the splitmix hash / bit-packed masks; bool
# for predication masks)
SANCTIONED_DTYPES = frozenset(("int32", "uint32", "bool"))

# primitive names that move data across the device/host boundary from
# INSIDE a compiled program
_TRANSFER_EXACT = frozenset(("infeed", "outfeed"))
_TRANSFER_SUBSTR = ("callback",)  # io_callback / pure_callback / debug_callback

_ALIAS_ATTR = "tf.aliasing_output"


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------
def _subjaxprs(param):
    import jax.extend.core as jc

    if isinstance(param, jc.ClosedJaxpr):
        return [param.jaxpr]
    if isinstance(param, jc.Jaxpr):
        return [param]
    if isinstance(param, (tuple, list)):
        out = []
        for p in param:
            out.extend(_subjaxprs(p))
        return out
    return []


def _iter_eqns(jaxpr):
    """Depth-first over every equation, descending into sub-jaxprs
    (pjit bodies, cond branches, while carry/body, scans)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _subjaxprs(param):
                yield from _iter_eqns(sub)


def _trace(ep):
    """(args, Traced) of one entry point at the canonical geometry.

    Uses the jit object's AOT ``.trace()`` so ONE trace serves every
    rule — the Traced carries both the jaxpr (dtype/transfer/g-last)
    and the lowering (donation); a separate ``.lower()`` call would
    re-trace each donating entry from scratch (review finding)."""
    args, kwargs = ep.build()
    return args, ep.fn.trace(*args, **kwargs)


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------
def _check_dtype(ep, closed, extra_ok: frozenset) -> List[Finding]:
    findings: List[Finding] = []
    seen: Dict[Tuple[str, str], int] = {}
    for eqn in _iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            name = str(dt)
            if name in SANCTIONED_DTYPES or name in extra_ok:
                continue
            key = (eqn.primitive.name, name)
            seen[key] = seen.get(key, 0) + 1
    for (prim, dtname), n in sorted(seen.items()):
        findings.append(
            Finding(
                ep.name, 0, "dtype",
                f"{prim} produces {dtname} (x{n}) outside the sanctioned "
                f"set {{int32, uint32, bool}} — ops/types.py int32 policy",
            )
        )
    # entry outputs must be strong-typed (weak outputs re-key the next
    # launch's trace — silent recompiles)
    weak = sum(
        1
        for v in closed.jaxpr.outvars
        if getattr(getattr(v, "aval", None), "weak_type", False)
    )
    if weak:
        findings.append(
            Finding(
                ep.name, 0, "dtype",
                f"{weak} weak-typed output(s): weak types drift across "
                "launches and force retraces",
            )
        )
    return findings


def _check_transfer(ep, closed) -> List[Finding]:
    hits = Counter()
    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in _TRANSFER_EXACT or any(
            s in name for s in _TRANSFER_SUBSTR
        ):
            hits[name] += 1
    return [
        Finding(
            ep.name, 0, "transfer",
            f"host-transfer primitive `{prim}` (x{n}) inside a compiled "
            "hot program — every sync stalls the launch for a host "
            "round trip",
        )
        for prim, n in sorted(hits.items())
    ]


def _leaf_keys(tree) -> Counter:
    import jax

    return Counter(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def _check_donation(ep, closed, args, traced) -> List[Finding]:
    """Expected aliases = maximal (shape, dtype) multiset matching of
    donated input leaves against output leaves; actual = aliasing
    attributes in the lowering.  actual < expected means XLA fell back
    to copy for a donation that could have aliased."""
    if not ep.donate:
        return []
    donated = Counter()
    for i in ep.donate:
        donated += _leaf_keys(args[i])
    outs = Counter(
        (tuple(v.aval.shape), str(v.aval.dtype))
        for v in closed.jaxpr.outvars
    )
    expected = sum(min(n, outs.get(k, 0)) for k, n in donated.items())
    with warnings.catch_warnings():
        # the "donated buffers were not usable" warning is exactly what
        # this rule quantifies; don't let it leak to callers
        warnings.simplefilter("ignore")
        text = traced.lower().as_text()
    actual = text.count(_ALIAS_ATTR)
    if actual < expected:
        return [
            Finding(
                ep.name, 0, "donation",
                f"only {actual}/{expected} shape-matched donated buffers "
                "alias in the lowering — donation fell back to copy "
                "(the ops/route.py aliased-zeros class)",
            )
        ]
    return []


def _check_g_last(ep, closed, G: int) -> List[Finding]:
    import jax.extend.core as jc

    seen: Dict[Tuple[str, tuple], int] = {}
    for eqn in _iter_eqns(closed.jaxpr):
        # constant fills (all-literal inputs, e.g. jnp.zeros/full in
        # constructors) fold under jit and carry no lane traffic
        if all(isinstance(iv, jc.Literal) for iv in eqn.invars):
            continue
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            shape = tuple(getattr(aval, "shape", ()))
            if len(shape) < 2 or G not in shape or shape[-1] == G:
                continue
            key = (eqn.primitive.name, shape)
            seen[key] = seen.get(key, 0) + 1
    return [
        Finding(
            ep.name, 0, "g-last",
            f"{prim} produces G-major {shape} (x{n}) in an internal-"
            "layout program — G must trail so int32 packs the 128-lane "
            "axis (ops/kernel.py layout contract)",
        )
        for (prim, shape), n in sorted(seen.items())
    ]


# ---------------------------------------------------------------------------
# registry completeness (AST over ops/*.py)
# ---------------------------------------------------------------------------
def _is_jit_decorator(dec: ast.expr) -> bool:
    """jax.jit / @functools.partial(jax.jit, ...) decorator shapes."""
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return True
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return True
    if isinstance(dec, ast.Call):
        f = dec.func
        if isinstance(f, ast.Attribute) and f.attr == "partial" and dec.args:
            return _is_jit_decorator(dec.args[0])
        return _is_jit_decorator(f)
    return False


def _jit_defs(ops_dir: str):
    """(module_basename, name, lineno) of every jitted definition:
    decorator form (@jax.jit / @functools.partial(jax.jit, ...)) AND
    assignment form (``fast = jax.jit(impl)`` or
    ``fast = functools.partial(jax.jit, ...)(impl)``) — the audit
    cannot cover what it cannot see, in either spelling."""
    out = []
    for fname in sorted(os.listdir(ops_dir)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(ops_dir, fname)
        with open(path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        mod = fname[:-3]
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_jit_decorator(d) for d in node.decorator_list):
                    out.append((mod, node.name, node.lineno))
            elif (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and _is_jit_decorator(node.value)
            ):
                out.append((mod, node.targets[0].id, node.lineno))
    return out


def _check_registry_complete(entries) -> List[Finding]:
    from ..ops import registry as _reg

    ops_dir = os.path.dirname(os.path.abspath(_reg.__file__))
    registered = {ep.name for ep in entries}
    findings = []
    for mod, fname, lineno in _jit_defs(ops_dir):
        if mod == "registry":
            continue  # the audit wrapper itself
        if f"{mod}.{fname}" not in registered:
            findings.append(
                Finding(
                    f"ops/{mod}.py", lineno, "unregistered-jit",
                    f"jitted `{fname}` is not in ops/registry.py — the "
                    "device-plane audit cannot cover what it cannot see",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def audit(entries=None, extra_ok: Iterable[str] = ()) -> List[Finding]:
    """Trace + check every registered entry point; returns findings.

    ``entries`` defaults to the full ops registry (tests pass fixture
    registries).  Tracing is abstract — no kernels compile, no device
    memory is touched — so the whole audit runs in seconds on CPU.
    """
    from ..ops import registry as _reg

    if entries is None:
        entries = _reg.ENTRY_POINTS
        check_complete = True
    else:
        check_complete = False
    extra = frozenset(extra_ok)
    G = _reg.CANON["G"]
    findings: List[Finding] = []
    for ep in entries:
        args, traced = _trace(ep)
        closed = traced.jaxpr
        findings.extend(_check_dtype(ep, closed, extra))
        findings.extend(_check_transfer(ep, closed))
        findings.extend(_check_donation(ep, closed, args, traced))
        if ep.g_last:
            findings.extend(_check_g_last(ep, closed, G))
    if check_complete:
        findings.extend(_check_registry_complete(entries))
    return findings


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="jaxcheck", description=__doc__.splitlines()[0]
    )
    ap.add_argument(
        "--baseline", default=None, help="baseline file to gate against"
    )
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the baseline from the current findings and exit 0",
    )
    args = ap.parse_args(argv)

    from ..ops import registry as _reg

    findings = audit()
    n_entries = len(_reg.ENTRY_POINTS)
    if args.update_baseline:
        if not args.baseline:
            ap.error("--update-baseline requires --baseline")
        write_baseline(args.baseline, findings)
        print(f"jaxcheck: baseline written ({len(findings)} findings)")
        return 0
    baseline = load_baseline(args.baseline) if args.baseline else {}
    new, stale = gate(findings, baseline)
    for f in new:
        print(f.render())
    for path, rule, allowed, now in stale:
        print(
            f"jaxcheck: note: baseline for {path} {rule} is {allowed}, "
            f"tree has {now} — ratchet it down",
            file=sys.stderr,
        )
    if new:
        print(
            f"jaxcheck: {len(new)} unbaselined finding(s) over {n_entries} "
            f"entry points ({len(findings)} total, baseline covers "
            f"{sum(baseline.values())})",
            file=sys.stderr,
        )
        return 1
    print(
        f"jaxcheck: clean over {n_entries} entry points"
        + (f" ({len(findings)} finding(s), all baselined)" if findings else "")
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
