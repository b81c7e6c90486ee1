"""raftlint: the project-native AST linter (stdlib ``ast``, no deps).

Rules (ids are stable — baseline entries and ignore comments key on them):

``guarded-by``
    A field whose defining assignment carries ``# guarded-by: <lock>``
    may only be accessed (read or write) via ``self.<field>`` inside a
    lexical ``with self.<lock>:`` block.  The function containing the
    defining assignment (normally ``__init__``) is exempt — state is
    unpublished there.  A ``def`` line carrying ``# guarded-by: <lock>``
    declares the whole function runs with the lock already held
    (callees of locked sections, e.g. ``_gc_extra``).

``block-under-lock``
    No potentially-unbounded blocking call lexically inside a ``with
    <lock>:`` body: ``.put(...)`` without a timeout/``block=False``
    (the exact shape of the PR 4 EventFanout close deadlock),
    zero-argument ``.get()`` (queue get; ``dict.get`` always takes a
    key), zero-argument ``.join()`` (thread join; ``str.join`` takes an
    iterable), ``time.sleep``, and socket ops (connect/accept/recv/
    send/sendall/recvfrom/sendto).  ``Condition.wait`` is fine — it
    releases the lock.

``determinism``
    The determinism plane (``faults.py``, ``balance/planner.py`` — the
    modules whose byte-deterministic event logs and seeded schedules
    the chaos/audit harnesses replay) must not read wall clocks or
    global rng: ``time.time()`` and module-level ``random.*`` calls are
    banned.  Allowed indirections: ``random.Random(seed)`` /
    ``random.SystemRandom`` construction, methods on rng instances,
    ``time.monotonic`` (deadlines, not identity) and ``time.sleep``.

``width-64``
    Codec modules (wire/tan/kvlogdb/snapshotio/gossip) pack protocol
    integers as uint64; every value feeding a ``Q`` slot of a
    ``struct`` pack must be masked ``& MASK64`` (docs/PARITY.md 64-bit
    policy) so encode wraps like the reference's uint64 instead of
    raising ``struct.error`` mid-persist.  Literals and ``len(...)``
    are exempt.

``host-sync``
    The device-plane modules (``ops/kernel.py``, ``ops/route.py`` —
    "pure int32 math, no host round-trips") must not force a
    device->host sync or a trace-time concretization: ``.item()``,
    ``int(...)``/``float(...)`` and ``np.asarray(...)``/``np.array(...)``
    applied to values are banned (each sync stalls the launch for a
    host round trip).  Static facts are
    exempt: literals, ``len(...)`` and anything reading ``.shape`` /
    ``.ndim`` / ``.size`` / ``.dtype``.  A ``# raftlint:
    ignore[host-sync] <reason>`` on a ``def`` line exempts that whole
    function (the documented host-side helpers, e.g. the
    ``build_route_tables`` numpy precompute).

``gateway-hot``
    In ``gateway/`` modules, a function whose ``def`` line carries a
    ``# gateway-hot`` comment is a declared per-request READ path
    (RoutingCache.lookup and friends): it must not acquire anything —
    no ``with <lock>:`` and no ``.acquire()``.  The sanctioned shape is
    the snapshot read (grab a copy-on-write dict/tuple in one attribute
    load; writers swap a fresh object under their own lock), the same
    discipline as ``metrics.export_text`` — a per-request lock on the
    routing table would serialize every client of every shard through
    one mutex.

``host-loop``
    In the host-plane modules (``ops/colocated.py``, ``ops/engine.py``,
    ``ops/hostplane.py``), a function whose ``def`` line carries a
    ``# hostplane-hot`` comment is a declared array-at-once pass over
    ALL rows of a generation: ``for`` statements and comprehensions
    are banned inside it — per-row Python in the plan/merge stages is
    exactly what the r6 vectorization removed (t_plan 887 s +
    t_updates 538 s of a 2,731 s 50k-shard election at 250k rows, r5)
    and must not rot back in; the r9
    update-lane assembly/sync functions (plan_update_sync and friends,
    ISSUE 13) carry the same marker.  A ``#
    raftlint: ignore[host-loop] <reason>`` on the ``def`` line (or on
    a pure-comment line directly above it) exempts a whole function —
    the documented scalar fallbacks and parity oracles (``*_scalar``
    twins in ops/hostplane.py).

``mesh-loop``
    The multi-chip launch path (functions marked ``# mesh-hot`` in the
    ops/ modules — the shard_map wrappers and their callers,
    docs/MULTICHIP.md) must stay free of per-device host work: the
    whole point of the sharded entry points is ONE dispatch for all
    chips, so a Python loop over ``jax.devices()``/``mesh.devices``
    or a ``jax.device_put``/``jax.device_get`` inside them re-opens
    the per-device host hop the collective lane exists to remove.
    Trace-time loops over static ranges (ring-shift unrolls) are fine.

``sync-budget``
    In the colocated launch path (``ops/colocated.py``,
    ``ops/engine.py``), a function whose ``def`` line carries a
    ``# sync-hot`` comment is a declared member of the launch
    pipeline's sync budget: every device->host round trip there stalls
    the launch and sequential syncs do not pipeline, so the budget is
    ONE commit-proving readback per generation (the split head/detail
    blob, requested at dispatch and collected at merge).  Bare
    ``np.asarray(<device value>)``, ``jax.device_get(...)`` and
    zero-arg ``.item()`` are banned inside such functions; the
    sanctioned readbacks (the blob collect, the documented fallback
    two-sync gather, debug-gated probes) carry a point
    ``# raftlint: ignore[sync-budget] <reason>``, as do host-built
    numpy conversions that never touch a device value.

``stream-read``
    The snapshot streaming path (``transport/chunk.py``,
    ``storage/snapshotter.py``, ``storage/snapshotio.py``,
    ``bigstate/``, ``tools.py``) exists so GB-scale state never
    materializes in memory: a zero-argument ``.read()`` buffers a whole
    stream and silently re-introduces the old whole-blob transfer.
    Every read must pass a size (bounded slice).  Deliberate whole-blob
    reads of small metadata carry a ``# raftlint: ignore[stream-read]
    <reason>``.

``obs-bound``
    The fleet-scope obs plane (``obs/fleetscope.py``,
    ``gateway/rpc.py``) answers ring-slice queries over the wire: a
    ``.tail(...)`` / ``.finished_tail(...)`` / ``.recorder_tail(...)``
    / ``.trace_spans(...)`` call without an explicit ``limit=`` keyword
    is an unbounded reply payload — one busy ring away from an
    8MB-frame teardown (docs/OBSERVABILITY.md "Fleet scope").

``import-hot``
    No function-level imports in the hot modules (``node.py``,
    ``request.py``, ``engine/``): a first call on the step/apply path
    must not pay an import-lock round trip.

``bare-except``
    No ``except:`` — it swallows KeyboardInterrupt/SystemExit.  The
    project idiom for intentional breadth is ``except Exception:`` with
    a ``# noqa: BLE001`` note.

``thread-discipline``
    Every ``threading.Thread(...)`` must pass ``name=`` (leak reports
    and timelines are useless full of ``Thread-12``) and an explicit
    ``daemon=`` (forcing the author to choose daemon-or-joined).

Point suppression: ``# raftlint: ignore[rule-id] <reason>`` on the
finding's line or on the first line of its enclosing statement.
Pre-existing accepted findings live in ``analysis/baseline.txt`` as
``<path> <rule> <count>`` lines; the gate fails only when a
(file, rule) count exceeds its baseline — zero new findings.
"""
from __future__ import annotations

import ast
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

GUARDED_RE = re.compile(r"#.*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")
IGNORE_RE = re.compile(r"#\s*raftlint:\s*ignore\[([a-z0-9-]+(?:\s*,\s*[a-z0-9-]+)*)\]")

MASK64 = 0xFFFFFFFFFFFFFFFF
MASK64_NAMES = {"MASK64", "_M64", "M64"}

# rule scoping (matched as posix-relpath suffixes/prefixes)
HOT_IMPORT_MODULES = (
    "dragonboat_tpu/node.py",
    "dragonboat_tpu/request.py",
    "dragonboat_tpu/engine/",
)
DETERMINISM_MODULES = (
    "dragonboat_tpu/faults.py",
    "dragonboat_tpu/balance/planner.py",
    # the production-day schedule builder: DayPlan.describe() is the
    # day's byte-determinism contract (docs/SCENARIO.md)
    "dragonboat_tpu/scenario/plan.py",
)
WIDTH_MODULES = (
    "dragonboat_tpu/transport/wire.py",
    "dragonboat_tpu/transport/gossip.py",
    "dragonboat_tpu/storage/tan.py",
    "dragonboat_tpu/storage/kvlogdb.py",
    "dragonboat_tpu/storage/snapshotio.py",
    # codec modules grown after the original rule list froze
    # (PR 20 wirecheck sweep): resume frames, rpc value/stats,
    # bigstate checkpoint/WAL records, journal framing, kvstore blocks
    "dragonboat_tpu/transport/tcp.py",
    "dragonboat_tpu/gateway/rpc.py",
    "dragonboat_tpu/bigstate/ondisk.py",
    "dragonboat_tpu/storage/journal.py",
    "dragonboat_tpu/storage/kvstore.py",
)
# the pure-device modules: host syncs are banned outright (engine.py /
# colocated.py legitimately sync — that is where launches read back)
HOST_SYNC_MODULES = (
    "dragonboat_tpu/ops/kernel.py",
    "dragonboat_tpu/ops/route.py",
)
# the snapshot streaming path: bounded reads only (docs/BIGSTATE.md)
STREAM_READ_MODULES = (
    "dragonboat_tpu/transport/chunk.py",
    "dragonboat_tpu/storage/snapshotter.py",
    "dragonboat_tpu/storage/snapshotio.py",
    "dragonboat_tpu/bigstate/",
    "dragonboat_tpu/tools.py",
)
# the serving front plane: `# gateway-hot` functions are lock-free
# snapshot-read paths (docs/GATEWAY.md "Routing")
GATEWAY_MODULES = ("dragonboat_tpu/gateway/",)
GATEWAY_HOT_RE = re.compile(r"#\s*gateway-hot\b")

# the colocated host plane: `# hostplane-hot` functions are
# array-at-once passes — no for-over-rows (docs/ANALYSIS.md).
# ops/engine.py joined for the ISSUE-13 update-lane assembly/sync
# functions (the base engine's merge tail shares the lane machinery).
HOSTPLANE_MODULES = (
    "dragonboat_tpu/ops/colocated.py",
    "dragonboat_tpu/ops/engine.py",
    "dragonboat_tpu/ops/hostplane.py",
)
HOSTPLANE_HOT_RE = re.compile(r"#\s*hostplane-hot\b")

# the colocated launch path: `# sync-hot` functions live inside the
# one-readback-per-generation sync budget
SYNC_BUDGET_MODULES = (
    "dragonboat_tpu/ops/colocated.py",
    "dragonboat_tpu/ops/engine.py",
)
SYNC_HOT_RE = re.compile(r"#\s*sync-hot\b")

# the multi-chip launch path: `# mesh-hot` functions dispatch ONE
# program for every chip — no per-device Python (docs/MULTICHIP.md)
MESH_MODULES = (
    "dragonboat_tpu/ops/kernel.py",
    "dragonboat_tpu/ops/route.py",
    "dragonboat_tpu/ops/engine.py",
    "dragonboat_tpu/ops/colocated.py",
)
MESH_HOT_RE = re.compile(r"#\s*mesh-hot\b")

# the fleet-scope obs plane: every obs reply slices its ring with an
# EXPLICIT limit (docs/OBSERVABILITY.md "Fleet scope")
OBS_REPLY_MODULES = (
    "dragonboat_tpu/obs/fleetscope.py",
    "dragonboat_tpu/gateway/rpc.py",
)
_OBS_TAIL_METHODS = {"tail", "finished_tail", "recorder_tail",
                     "trace_spans"}

# attributes whose read is a static (trace-time, host-free) fact
_STATIC_FACT_ATTRS = {"shape", "ndim", "size", "dtype"}
_NUMPY_ALIASES = {"np", "numpy", "_np"}

BLOCKING_SOCKET_METHODS = {
    "connect", "accept", "recv", "send", "sendall", "recvfrom", "sendto",
}
# names that make a `with X:` item count as a lock for block-under-lock:
# the FINAL underscore-segment must itself be a lock word — an
# unanchored `lock$` would swallow clock/block/unlock (review finding)
LOCKISH_RE = re.compile(r"(?:^|_)(?:lock|qlock|glock|mu|mutex)$")


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line} {self.rule} {self.message}"


def _module_matches(relpath: str, scopes) -> bool:
    p = relpath.replace(os.sep, "/")
    for s in scopes:
        if s.endswith("/"):
            if f"/{s}" in f"/{p}" or p.startswith(s):
                return True
        elif p == s or p.endswith("/" + s) or p.endswith(s):
            return True
    return False


def _parse_q_slots(fmt: str) -> Optional[List[int]]:
    """Indices of pack() args that land in 64-bit ('Q'/'q') slots.
    Returns None for formats raftlint cannot map (e.g. 's' with counts,
    which consumes one arg per run)."""
    slots: List[int] = []
    arg_i = 0
    count = ""
    for ch in fmt:
        if ch in "<>=!@ ":
            continue
        if ch.isdigit():
            count += ch
            continue
        n = int(count) if count else 1
        count = ""
        if ch in "sp":
            # one arg regardless of count
            arg_i += 1
            continue
        if ch == "x":
            continue
        for _ in range(n):
            if ch in "Qq":
                slots.append(arg_i)
            arg_i += 1
    return slots


def _is_masked64(node: ast.AST) -> bool:
    """True for expressions the width rule accepts in a Q slot."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Name) and f.id == "len":
            return True
        if isinstance(f, ast.Attribute) and f.attr == "crc32":
            return True
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitAnd):
        for side in (node.left, node.right):
            if isinstance(side, ast.Name) and side.id in MASK64_NAMES:
                return True
            if isinstance(side, ast.Attribute) and side.attr in MASK64_NAMES:
                return True
            if isinstance(side, ast.Constant) and side.value == MASK64:
                return True
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, relpath: str, source: str, tree: ast.Module):
        self.relpath = relpath.replace(os.sep, "/")
        self.lines = source.splitlines()
        self.tree = tree
        self.findings: List[Finding] = []
        # rule scoping resolved once
        self.check_imports = _module_matches(self.relpath, HOT_IMPORT_MODULES)
        self.check_determinism = _module_matches(
            self.relpath, DETERMINISM_MODULES
        )
        self.check_width = _module_matches(self.relpath, WIDTH_MODULES)
        self.check_host_sync = _module_matches(
            self.relpath, HOST_SYNC_MODULES
        )
        self.check_stream_read = _module_matches(
            self.relpath, STREAM_READ_MODULES
        )
        self.check_gateway = _module_matches(self.relpath, GATEWAY_MODULES)
        self.check_hostplane = _module_matches(
            self.relpath, HOSTPLANE_MODULES
        )
        self.check_sync_budget = _module_matches(
            self.relpath, SYNC_BUDGET_MODULES
        )
        self.check_mesh = _module_matches(self.relpath, MESH_MODULES)
        self.check_obs_bound = _module_matches(
            self.relpath, OBS_REPLY_MODULES
        )
        # count of enclosing `# gateway-hot` / `# hostplane-hot` /
        # `# sync-hot` functions (nested defs inside a hot function
        # inherit the discipline)
        self._hot_depth = 0
        self._hp_depth = 0
        self._sync_depth = 0
        self._mesh_depth = 0
        # file-wide guarded fields: attr -> (lock attr, defining func node)
        self.guarded: Dict[str, Tuple[str, Optional[ast.AST]]] = {}
        # module-level struct.Struct assignments: name -> Q slot indices
        self.structs: Dict[str, List[int]] = {}
        # walk state
        self._held: List[str] = []  # lock names currently held (lexically)
        # locks held specifically via `with self.<lock>:` — the only form
        # that satisfies guarded-by (holding ANOTHER object's same-named
        # lock is exactly the bug class the rule exists to catch)
        self._held_self: List[str] = []
        self._func_stack: List[ast.AST] = []  # enclosing function defs
        self._stmt_stack: List[int] = []  # enclosing statement linenos

    # -- plumbing ---------------------------------------------------------

    def _line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def _guard_annot(self, node: ast.AST) -> Optional[str]:
        """The guarded-by lock name annotated on a node's line, or on a
        pure-comment line directly above it."""
        m = GUARDED_RE.search(self._line(node.lineno))
        if m is None and self._line(node.lineno - 1).strip().startswith("#"):
            m = GUARDED_RE.search(self._line(node.lineno - 1))
        return m.group(1) if m else None

    def _suppressed(self, rule: str, lineno: int) -> bool:
        candidates = {lineno}
        if self._stmt_stack:
            candidates.add(self._stmt_stack[-1])
        # a pure-comment line directly above the finding/statement also
        # counts (the ignore-next-line style keeps code lines readable)
        for ln in list(candidates):
            if self._line(ln - 1).strip().startswith("#"):
                candidates.add(ln - 1)
        for ln in candidates:
            m = IGNORE_RE.search(self._line(ln))
            if m and rule in {r.strip() for r in m.group(1).split(",")}:
                return True
        return False

    def _emit(self, rule: str, lineno: int, message: str) -> None:
        if not self._suppressed(rule, lineno):
            self.findings.append(Finding(self.relpath, lineno, rule, message))

    # -- pass 1: collect annotations and struct tables --------------------

    def collect(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                lock = self._guard_annot(node)
                if lock:
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for t in targets:
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                        ):
                            self.guarded[t.attr] = (lock, None)
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr == "Struct"
                    and node.value.args
                    and isinstance(node.value.args[0], ast.Constant)
                    and isinstance(node.value.args[0].value, str)
                ):
                    slots = _parse_q_slots(node.value.args[0].value)
                    if slots:
                        self.structs[node.targets[0].id] = slots
        # resolve each guarded field's defining function (the function
        # whose body contains the annotated assignment)
        for func in ast.walk(self.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(func):
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    if self._guard_annot(node) is None:
                        continue
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for t in targets:
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                            and t.attr in self.guarded
                            and self.guarded[t.attr][1] is None
                        ):
                            self.guarded[t.attr] = (
                                self.guarded[t.attr][0],
                                func,
                            )

    # -- pass 2: the walk -------------------------------------------------

    def run(self) -> List[Finding]:
        self.collect()
        self.visit(self.tree)
        return self.findings

    def visit(self, node: ast.AST) -> None:
        pushed_stmt = False
        if isinstance(node, ast.stmt):
            self._stmt_stack.append(node.lineno)
            pushed_stmt = True
        try:
            super().visit(node)
        finally:
            if pushed_stmt:
                self._stmt_stack.pop()

    # ---- functions: reset lexical lock context, track nesting ----------

    def _visit_func(self, node) -> None:
        held, self._held = self._held, []
        held_self, self._held_self = self._held_self, []
        # a `# guarded-by: <lock>` on the def line declares the function
        # runs with the lock already held (the caller's self.<lock>)
        m = GUARDED_RE.search(self._line(node.lineno))
        if m:
            self._held.append(m.group(1))
            self._held_self.append(m.group(1))
        hot = self.check_gateway and bool(
            GATEWAY_HOT_RE.search(self._line(node.lineno))
        )
        if hot:
            self._hot_depth += 1
        hp = self.check_hostplane and bool(
            HOSTPLANE_HOT_RE.search(self._line(node.lineno))
        )
        if hp:
            self._hp_depth += 1
        sh = self.check_sync_budget and bool(
            SYNC_HOT_RE.search(self._line(node.lineno))
        )
        if sh:
            self._sync_depth += 1
        mh = self.check_mesh and bool(
            MESH_HOT_RE.search(self._line(node.lineno))
        )
        if mh:
            self._mesh_depth += 1
        self._func_stack.append(node)
        try:
            self.generic_visit(node)
        finally:
            self._func_stack.pop()
            self._held = held
            self._held_self = held_self
            if hot:
                self._hot_depth -= 1
            if hp:
                self._hp_depth -= 1
            if sh:
                self._sync_depth -= 1
            if mh:
                self._mesh_depth -= 1

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_func(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_func(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        held, self._held = self._held, []
        held_self, self._held_self = self._held_self, []
        try:
            self.generic_visit(node)
        finally:
            self._held = held
            self._held_self = held_self

    # ---- with: enter/exit lock scopes ----------------------------------

    @staticmethod
    def _lock_name(expr: ast.AST) -> Optional[str]:
        """The lock attr/name of a with-item, or None if not lock-like."""
        target = expr
        if isinstance(target, ast.Call):
            return None  # with open(...) etc.
        if isinstance(target, ast.Attribute):
            name = target.attr
        elif isinstance(target, ast.Name):
            name = target.id
        else:
            return None
        return name if LOCKISH_RE.search(name) else None

    def visit_With(self, node: ast.With) -> None:
        entered: List[str] = []
        entered_self: List[str] = []
        for item in node.items:
            expr = item.context_expr
            ln = self._lock_name(expr)
            if ln is not None:
                if self._hot_depth:
                    self._emit(
                        "gateway-hot",
                        node.lineno,
                        f"`with {ln}:` inside a # gateway-hot read path "
                        "(snapshot-read the copy-on-write table instead; "
                        "docs/GATEWAY.md)",
                    )
                entered.append(ln)
                if (
                    isinstance(expr, ast.Attribute)
                    and isinstance(expr.value, ast.Name)
                    and expr.value.id == "self"
                ):
                    entered_self.append(ln)
        self._held.extend(entered)
        self._held_self.extend(entered_self)
        try:
            self.generic_visit(node)
        finally:
            for _ in entered:
                self._held.pop()
            for _ in entered_self:
                self._held_self.pop()

    # ---- guarded-by -----------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (
            isinstance(node.value, ast.Name)
            and node.value.id == "self"
            and node.attr in self.guarded
        ):
            lock, def_func = self.guarded[node.attr]
            in_def_func = def_func is not None and any(
                f is def_func for f in self._func_stack
            )
            if not in_def_func and lock not in self._held_self:
                self._emit(
                    "guarded-by",
                    node.lineno,
                    f"self.{node.attr} accessed outside `with self.{lock}:`",
                )
        self.generic_visit(node)

    # ---- block-under-lock + determinism + width (all calls) ------------

    def visit_Call(self, node: ast.Call) -> None:
        if self._hot_depth and isinstance(node.func, ast.Attribute) and (
            node.func.attr == "acquire"
        ):
            self._emit(
                "gateway-hot",
                node.lineno,
                ".acquire() inside a # gateway-hot read path "
                "(snapshot-read discipline; docs/GATEWAY.md)",
            )
        if self._held:
            self._check_blocking(node)
        if self.check_determinism:
            self._check_determinism(node)
        if self.check_width:
            self._check_width(node)
        if self.check_host_sync:
            self._check_host_sync(node)
        if self.check_stream_read:
            self._check_stream_read(node)
        if self.check_obs_bound:
            self._check_obs_bound(node)
        if self._sync_depth:
            self._check_sync_budget(node)
        if self._mesh_depth:
            self._check_mesh_call(node)
        self._check_thread(node)
        self.generic_visit(node)

    def _kw(self, node: ast.Call, name: str) -> Optional[ast.AST]:
        for kw in node.keywords:
            if kw.arg == name:
                return kw.value
        return None

    def _check_blocking(self, node: ast.Call) -> None:
        f = node.func
        if not isinstance(f, ast.Attribute):
            return
        meth = f.attr
        lineno = node.lineno
        if meth == "put" and len(node.args) == 1:
            # one positional arg = the queue.put(item) shape; kv-store
            # put(key, value) is a dict write, not a blocking call
            blk = self._kw(node, "block")
            if (
                self._kw(node, "timeout") is None
                and not (isinstance(blk, ast.Constant) and blk.value is False)
            ):
                self._emit(
                    "block-under-lock",
                    lineno,
                    "blocking .put() under a held lock (use put_nowait or "
                    "a timeout; the EventFanout close deadlock shape)",
                )
        elif meth == "get" and not node.args and not node.keywords:
            self._emit(
                "block-under-lock",
                lineno,
                "blocking zero-arg .get() under a held lock",
            )
        elif meth == "join" and not node.args and self._kw(node, "timeout") is None:
            self._emit(
                "block-under-lock",
                lineno,
                "unbounded .join() under a held lock",
            )
        elif meth == "sleep" and isinstance(f.value, ast.Name) and (
            f.value.id in ("time", "_time")
        ):
            self._emit(
                "block-under-lock", lineno, "time.sleep under a held lock"
            )
        elif meth in BLOCKING_SOCKET_METHODS and isinstance(
            f.value, (ast.Name, ast.Attribute)
        ):
            recv = f.value.attr if isinstance(f.value, ast.Attribute) else f.value.id
            if "sock" in recv or recv == "s":
                self._emit(
                    "block-under-lock",
                    lineno,
                    f"socket .{meth}() under a held lock",
                )

    @staticmethod
    def _is_static_fact(node: ast.AST) -> bool:
        """Expressions that concretize without touching device data:
        literals, len(...), and anything whose value flows from a
        .shape/.ndim/.size/.dtype read (e.g. int(x.shape[0]))."""
        if all(
            isinstance(
                n,
                (ast.Constant, ast.BinOp, ast.UnaryOp, ast.operator,
                 ast.unaryop),
            )
            for n in ast.walk(node)
        ):
            return True  # constant arithmetic, e.g. int(2**31 - 1)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "len"
        ):
            return True
        return any(
            isinstance(n, ast.Attribute) and n.attr in _STATIC_FACT_ATTRS
            for n in ast.walk(node)
        )

    def _func_exempt(self, rule: str) -> bool:
        """A `# raftlint: ignore[<rule>] <reason>` on an enclosing def
        line — or on a pure-comment line directly above it (the same
        ignore-next-line style `_suppressed` accepts) — exempts the
        whole function: the documented host-side helpers living inside
        a device module (host-sync) and the documented scalar
        fallbacks / parity oracles of the host plane (host-loop).
        Decorated defs are also covered via the decorator lines."""
        for func in self._func_stack:
            lines = {func.lineno}
            if self._line(func.lineno - 1).strip().startswith("#"):
                lines.add(func.lineno - 1)
            for ln in lines:
                m = IGNORE_RE.search(self._line(ln))
                if m and rule in {
                    r.strip() for r in m.group(1).split(",")
                }:
                    return True
        return False

    def _host_sync_func_exempt(self) -> bool:
        return self._func_exempt("host-sync")

    def _check_host_sync(self, node: ast.Call) -> None:
        f = node.func
        hit = None
        if isinstance(f, ast.Attribute) and f.attr == "item" and not node.args:
            hit = ".item() forces a device->host sync"
        elif (
            isinstance(f, ast.Name)
            and f.id in ("int", "float")
            and len(node.args) == 1
            and not self._is_static_fact(node.args[0])
        ):
            hit = (
                f"{f.id}(...) concretizes a (potential) device value — "
                "a host sync on the device plane"
            )
        elif (
            isinstance(f, ast.Attribute)
            and f.attr in ("asarray", "array")
            and isinstance(f.value, ast.Name)
            and f.value.id in _NUMPY_ALIASES
        ):
            hit = f"np.{f.attr}(...) materializes a device value on host"
        if hit is None or self._host_sync_func_exempt():
            return
        self._emit(
            "host-sync",
            node.lineno,
            hit + " (each sync stalls the launch for a host round trip)",
        )

    def _check_sync_budget(self, node: ast.Call) -> None:
        """Bare device->host syncs inside a `# sync-hot` function (the
        colocated launch pipeline's one-readback-per-generation
        budget).  Each stray sync is one more host round trip, and it
        defeats the double-buffered overlap."""
        f = node.func
        hit = None
        if (
            isinstance(f, ast.Attribute)
            and f.attr in ("asarray", "array")
            and isinstance(f.value, ast.Name)
            and f.value.id in _NUMPY_ALIASES
        ):
            hit = (
                f"bare np.{f.attr}(...) in the launch pipeline — a"
                " potential device readback outside the blob sync"
            )
        elif (
            isinstance(f, ast.Attribute)
            and f.attr == "device_get"
        ):
            hit = "jax.device_get(...) outside the annotated blob readback"
        elif (
            isinstance(f, ast.Attribute)
            and f.attr == "item"
            and not node.args
        ):
            hit = ".item() forces an extra device->host round trip"
        if hit is None or self._func_exempt("sync-budget"):
            return
        self._emit(
            "sync-budget",
            node.lineno,
            hit + " (the launch budget is ONE commit-proving readback "
            "per generation)",
        )

    def _check_stream_read(self, node: ast.Call) -> None:
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "read"
            and not node.args
            and not node.keywords
        ):
            self._emit(
                "stream-read",
                node.lineno,
                "zero-argument .read() buffers a whole stream in memory "
                "(pass a bounded size; the streaming path must handle "
                "state larger than RAM — docs/BIGSTATE.md)",
            )

    def _check_obs_bound(self, node: ast.Call) -> None:
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr in _OBS_TAIL_METHODS
            and self._kw(node, "limit") is None
        ):
            self._emit(
                "obs-bound",
                node.lineno,
                f".{f.attr}() without an explicit limit= is an unbounded "
                "obs reply payload (every ring slice must be bounded — "
                "docs/OBSERVABILITY.md \"Fleet scope\")",
            )

    def _check_determinism(self, node: ast.Call) -> None:
        f = node.func
        if not isinstance(f, ast.Attribute) or not isinstance(f.value, ast.Name):
            return
        mod = f.value.id
        if mod in ("time", "_time") and f.attr == "time":
            self._emit(
                "determinism",
                node.lineno,
                "naked wall clock time.time() in the determinism plane "
                "(use the seeded schedule / time.monotonic deadlines)",
            )
        elif mod in ("random", "_random") and f.attr not in (
            "Random",
            "SystemRandom",
        ):
            self._emit(
                "determinism",
                node.lineno,
                f"global rng random.{f.attr}() in the determinism plane "
                "(use a seeded random.Random instance)",
            )

    def _check_width(self, node: ast.Call) -> None:
        f = node.func
        if not isinstance(f, ast.Attribute) or f.attr != "pack":
            return
        slots: Optional[List[int]] = None
        if isinstance(f.value, ast.Name):
            if f.value.id == "struct":
                if node.args and isinstance(node.args[0], ast.Constant) and (
                    isinstance(node.args[0].value, str)
                ):
                    slots = [
                        i + 1
                        for i in _parse_q_slots(node.args[0].value) or []
                    ]
            elif f.value.id in self.structs:
                slots = self.structs[f.value.id]
        if not slots:
            return
        for i in slots:
            if i < len(node.args) and not _is_masked64(node.args[i]):
                self._emit(
                    "width-64",
                    node.lineno,
                    "u64 pack of unmasked value (append `& MASK64`; "
                    "docs/PARITY.md 64-bit policy)",
                )

    # ---- host-loop (for-over-rows in # hostplane-hot functions) ---------

    def _check_host_loop(self, node: ast.AST, what: str) -> None:
        if not self._hp_depth or self._func_exempt("host-loop"):
            return
        self._emit(
            "host-loop",
            node.lineno,
            f"{what} inside a # hostplane-hot array pass (use numpy "
            "array ops over all rows; per-row Python is the t_plan/"
            "t_updates cost the r6 vectorization removed — "
            "docs/ANALYSIS.md)",
        )

    def visit_For(self, node: ast.For) -> None:
        self._check_host_loop(node, "`for` loop")
        self._check_mesh_loop(node)
        self.generic_visit(node)

    def visit_AsyncFor(self, node: ast.AsyncFor) -> None:
        self._check_host_loop(node, "`async for` loop")
        self.generic_visit(node)

    def visit_ListComp(self, node: ast.ListComp) -> None:
        self._check_host_loop(node, "list comprehension")
        self.generic_visit(node)

    def visit_SetComp(self, node: ast.SetComp) -> None:
        self._check_host_loop(node, "set comprehension")
        self.generic_visit(node)

    def visit_DictComp(self, node: ast.DictComp) -> None:
        self._check_host_loop(node, "dict comprehension")
        self.generic_visit(node)

    def visit_GeneratorExp(self, node: ast.GeneratorExp) -> None:
        self._check_host_loop(node, "generator expression")
        self.generic_visit(node)

    # ---- mesh-loop (per-device host work in # mesh-hot functions) -------

    @staticmethod
    def _mentions_devices(expr: ast.AST) -> bool:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Attribute) and sub.attr in (
                "devices", "local_devices", "device_set",
            ):
                return True
            if isinstance(sub, ast.Name) and sub.id in (
                "devices", "local_devices",
            ):
                return True
        return False

    def _check_mesh_loop(self, node) -> None:
        if not self._mesh_depth or self._func_exempt("mesh-loop"):
            return
        if self._mentions_devices(node.iter):
            self._emit(
                "mesh-loop",
                node.lineno,
                "Python iteration over devices inside a # mesh-hot "
                "function — the sharded launch path dispatches ONE "
                "program for every chip (docs/MULTICHIP.md); per-device "
                "host loops re-open the host hop the collective lane "
                "removes",
            )

    def _check_mesh_call(self, node: ast.Call) -> None:
        if not self._mesh_depth or self._func_exempt("mesh-loop"):
            return
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in (
            "device_put", "device_get",
        ):
            self._emit(
                "mesh-loop",
                node.lineno,
                f"`{f.attr}` inside a # mesh-hot function — host<->device "
                "transfers belong outside the sharded launch path "
                "(docs/MULTICHIP.md; the transfer-free gate is also "
                "machine-checked by jaxcheck over the mesh entries)",
            )

    # ---- hygiene --------------------------------------------------------

    def _check_import(self, node) -> None:
        if self.check_imports and self._func_stack:
            self._emit(
                "import-hot",
                node.lineno,
                "function-level import in a hot module (hoist to module "
                "level; the step/apply path must not pay the import lock)",
            )

    def visit_Import(self, node: ast.Import) -> None:
        self._check_import(node)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        self._check_import(node)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self._emit(
                "bare-except",
                node.lineno,
                "bare `except:` (catches KeyboardInterrupt/SystemExit; "
                "use `except Exception:` at most)",
            )
        self.generic_visit(node)

    def _check_thread(self, value: ast.Call) -> None:
        f = value.func
        is_thread = (
            isinstance(f, ast.Attribute)
            and f.attr == "Thread"
            and isinstance(f.value, ast.Name)
            and f.value.id == "threading"
        ) or (isinstance(f, ast.Name) and f.id == "Thread")
        if not is_thread:
            return
        kwargs = {kw.arg for kw in value.keywords}
        if "name" not in kwargs:
            self._emit(
                "thread-discipline",
                value.lineno,
                "thread started without name= (leak reports and timelines "
                "need named threads)",
            )
        if "daemon" not in kwargs:
            self._emit(
                "thread-discipline",
                value.lineno,
                "thread without explicit daemon= (choose daemon-or-joined)",
            )


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def lint_source(source: str, relpath: str) -> List[Finding]:
    """Lint one source blob as if it lived at ``relpath`` (fixtures use
    fake paths to trigger module-scoped rules)."""
    tree = ast.parse(source, filename=relpath)
    return _Linter(relpath, source, tree).run()


def _iter_py_files(paths) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [
                    d for d in dirs if d != "__pycache__" and not d.startswith(".")
                ]
                out.extend(
                    os.path.join(root, f) for f in files if f.endswith(".py")
                )
        elif p.endswith(".py"):
            out.append(p)
    return sorted(out)


def lint_paths(paths) -> List[Finding]:
    findings: List[Finding] = []
    for path in _iter_py_files(paths):
        rel = os.path.relpath(path).replace(os.sep, "/")
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            findings.extend(lint_source(src, rel))
        except SyntaxError as e:
            findings.append(
                Finding(rel, e.lineno or 0, "parse-error", str(e.msg))
            )
    return findings


def _counts(findings) -> Dict[Tuple[str, str], int]:
    out: Dict[Tuple[str, str], int] = {}
    for f in findings:
        out[(f.path, f.rule)] = out.get((f.path, f.rule), 0) + 1
    return out


def load_baseline(path: str) -> Dict[Tuple[str, str], int]:
    """``<path> <rule> <count>`` lines; '#' comments and blanks ignored."""
    out: Dict[Tuple[str, str], int] = {}
    if not os.path.exists(path):
        return out
    with open(path, "r", encoding="utf-8") as f:
        for raw in f:
            line = raw.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split()
            if len(parts) != 3:
                raise ValueError(f"bad baseline line: {raw.rstrip()}")
            out[(parts[0], parts[1])] = int(parts[2])
    return out


def write_baseline(path: str, findings) -> None:
    counts = _counts(findings)
    with open(path, "w", encoding="utf-8") as f:
        f.write(
            "# raftlint baseline: accepted pre-existing findings as\n"
            "# `<path> <rule> <count>` — the gate fails only on counts\n"
            "# ABOVE these.  Shrink it whenever you clean a finding up;\n"
            "# never grow it to sneak new debt in.\n"
        )
        for (p, rule), n in sorted(counts.items()):
            f.write(f"{p} {rule} {n}\n")


def gate(findings, baseline: Dict[Tuple[str, str], int]):
    """(new_findings, stale_entries): findings beyond baseline counts, and
    baseline entries whose debt shrank (candidates for ratcheting down)."""
    counts = _counts(findings)
    new: List[Finding] = []
    for (path, rule), n in sorted(counts.items()):
        allowed = baseline.get((path, rule), 0)
        if n > allowed:
            per = [f for f in findings if f.path == path and f.rule == rule]
            # report the whole group: line numbers drift, so naming
            # exactly the "new" ones is guesswork — show all candidates
            new.extend(per)
    stale = [
        (path, rule, allowed, counts.get((path, rule), 0))
        for (path, rule), allowed in sorted(baseline.items())
        if counts.get((path, rule), 0) < allowed
    ]
    return new, stale


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="raftlint", description=__doc__.splitlines()[0]
    )
    ap.add_argument("paths", nargs="*", default=["dragonboat_tpu"])
    ap.add_argument("--baseline", default=None, help="baseline file to gate against")
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the baseline from the current findings and exit 0",
    )
    args = ap.parse_args(argv)

    findings = lint_paths(args.paths or ["dragonboat_tpu"])
    if args.update_baseline:
        if not args.baseline:
            ap.error("--update-baseline requires --baseline")
        write_baseline(args.baseline, findings)
        print(f"raftlint: baseline written ({len(findings)} findings)")
        return 0

    baseline = load_baseline(args.baseline) if args.baseline else {}
    new, stale = gate(findings, baseline)
    for f in new:
        print(f.render())
    for path, rule, allowed, now in stale:
        print(
            f"raftlint: note: baseline for {path} {rule} is {allowed}, "
            f"tree has {now} — ratchet it down",
            file=sys.stderr,
        )
    if new:
        print(
            f"raftlint: {len(new)} unbaselined finding(s) "
            f"({len(findings)} total, baseline covers "
            f"{sum(baseline.values())})",
            file=sys.stderr,
        )
        return 1
    print(
        f"raftlint: clean ({len(findings)} finding(s), all baselined)"
        if findings
        else "raftlint: clean"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
