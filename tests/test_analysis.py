"""analysis/ correctness-tooling tests: raftlint true-positive fixtures
(every rule must catch a seeded violation), baseline/ignore machinery,
the zero-unbaselined-findings tree gate, and the lock-order witness
(cycle detection with witness stacks, slow-wait flagging, Condition
integration, install/uninstall hygiene)."""
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dragonboat_tpu.analysis import lockcheck, raftlint
from dragonboat_tpu.analysis.raftlint import (
    Finding,
    gate,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# guarded-by
# ---------------------------------------------------------------------------
GUARDED_SRC = '''
import threading

class Node:
    def __init__(self):
        self._qlock = threading.Lock()
        self._proposals = []  # guarded-by: _qlock

    def ok(self, e):
        with self._qlock:
            self._proposals.append(e)

    def bad(self, e):
        self._proposals.append(e)  # unlocked access

    def held_throughout(self):  # guarded-by: _qlock
        return len(self._proposals)
'''


def test_guarded_by_catches_unlocked_access():
    fs = lint_source(GUARDED_SRC, "dragonboat_tpu/node.py")
    assert [f.rule for f in fs] == ["guarded-by"]
    (f,) = fs
    assert "_proposals" in f.message and "_qlock" in f.message
    # the finding names the unlocked line in bad(), not ok()/__init__
    assert "self._proposals.append(e)  # unlocked access" in (
        GUARDED_SRC.splitlines()[f.line - 1]
    )


def test_guarded_by_def_annotation_declares_lock_held():
    # held_throughout carries the def-line annotation -> no finding there
    fs = lint_source(GUARDED_SRC, "dragonboat_tpu/node.py")
    assert all("held_throughout" not in GUARDED_SRC.splitlines()[f.line - 1]
               for f in fs)


def test_guarded_by_ignore_comment_suppresses():
    src = GUARDED_SRC.replace(
        "self._proposals.append(e)  # unlocked access",
        "self._proposals.append(e)  # raftlint: ignore[guarded-by] test",
    )
    assert lint_source(src, "dragonboat_tpu/node.py") == []


def test_guarded_by_ignore_next_line_style():
    src = GUARDED_SRC.replace(
        "        self._proposals.append(e)  # unlocked access",
        "        # raftlint: ignore[guarded-by] reason\n"
        "        self._proposals.append(e)",
    )
    assert lint_source(src, "dragonboat_tpu/node.py") == []


def test_guarded_by_annotation_above_assignment():
    src = '''
class H:
    def __init__(self):
        self._lock = __import__("threading").Lock()
        # shard map; guarded-by: _lock
        self._nodes = {}

    def bad(self):
        return self._nodes.get(1)
'''
    fs = lint_source(src, "dragonboat_tpu/nodehost.py")
    assert rules_of(fs) == {"guarded-by"}


def test_guarded_by_rejects_holding_another_objects_lock():
    """Holding a PEER object's same-named lock must NOT satisfy the
    guard — mutating one node's _qlock-guarded queue while holding
    another node's _qlock is exactly the bug class the rule exists to
    catch (review finding)."""
    src = '''
import threading

class Node:
    def __init__(self):
        self._qlock = threading.Lock()
        self._items = []  # guarded-by: _qlock

    def cross_drain(self, other):
        with other._qlock:
            self._items.append(1)
'''
    fs = lint_source(src, "dragonboat_tpu/node.py")
    assert rules_of(fs) == {"guarded-by"}


def test_guarded_by_lambda_body_is_not_covered_by_enclosing_with():
    # a lambda defined under the lock RUNS later, without it
    src = '''
class H:
    def __init__(self):
        self._lock = __import__("threading").Lock()
        self._m = {}  # guarded-by: _lock

    def arm(self, reg):
        with self._lock:
            reg.gauge("x", lambda: len(self._m))
'''
    fs = lint_source(src, "dragonboat_tpu/nodehost.py")
    assert rules_of(fs) == {"guarded-by"}


# ---------------------------------------------------------------------------
# block-under-lock — incl. the PR 4 EventFanout deadlock reconstruction
# ---------------------------------------------------------------------------
EVENTFANOUT_PR4_SRC = '''
import queue
import threading

class EventFanout:
    """Reconstruction of the PR 4 close() deadlock: a BLOCKING put on a
    full queue while holding the fanout lock — the drain thread exits
    via the stop flag with the queue still full, so the put never
    returns and close() hangs forever."""

    def __init__(self):
        self._lock = threading.Lock()
        self._q = queue.Queue(maxsize=2)
        self._thread = threading.Thread(target=self._main, daemon=True,
                                        name="ev")

    def close(self):
        with self._lock:
            self._q.put(None)      # the deadlock: blocking put under lock
            self._thread.join()    # and an unbounded join under lock
'''


def test_block_under_lock_catches_pr4_eventfanout_shape():
    fs = lint_source(EVENTFANOUT_PR4_SRC, "dragonboat_tpu/events.py")
    msgs = [f.message for f in fs if f.rule == "block-under-lock"]
    assert len(msgs) == 2
    assert any(".put()" in m for m in msgs)
    assert any(".join()" in m for m in msgs)


def test_block_under_lock_allows_nowait_timeout_and_unlocked():
    src = '''
class F:
    def ok(self):
        with self._lock:
            self._q.put_nowait(None)
            self._q.put(None, timeout=0.5)
            self._q.get(timeout=0.2)
            self._thread.join(timeout=1.0)
    def also_ok(self):
        self._q.put(None)  # not under a lock: fine
'''
    assert lint_source(src, "dragonboat_tpu/events.py") == []


def test_block_under_lock_sleep_and_zero_arg_get():
    src = '''
import time
class F:
    def bad(self):
        with self._mu:
            time.sleep(0.1)
            item = self._q.get()
'''
    fs = lint_source(src, "dragonboat_tpu/x.py")
    assert len([f for f in fs if f.rule == "block-under-lock"]) == 2


def test_lockish_names_are_segment_anchored():
    """`clock`/`block`/`unlock` context managers are NOT locks — an
    unanchored lock$ match would force bogus ignores (review finding)."""
    src = '''
import time
class F:
    def fine(self):
        with self.clock:
            time.sleep(0.1)
        with self.block:
            time.sleep(0.1)
        with self.unlock:
            time.sleep(0.1)
    def caught(self):
        with self._nodes_lock:
            time.sleep(0.1)
'''
    fs = lint_source(src, "dragonboat_tpu/x.py")
    assert len(fs) == 1 and fs[0].rule == "block-under-lock"


# ---------------------------------------------------------------------------
# determinism plane
# ---------------------------------------------------------------------------
def test_determinism_catches_wall_clock_and_global_rng():
    src = '''
import random
import time

def schedule():
    t = time.time()
    return t + random.random()
'''
    fs = lint_source(src, "dragonboat_tpu/faults.py")
    assert len([f for f in fs if f.rule == "determinism"]) == 2


def test_determinism_allows_seeded_rng_and_monotonic():
    src = '''
import random
import time

def schedule(seed):
    rng = random.Random(seed)
    deadline = time.monotonic() + rng.uniform(0, 1)
    time.sleep(0.01)
    return deadline
'''
    assert lint_source(src, "dragonboat_tpu/balance/planner.py") == []


def test_determinism_rule_scoped_to_plane_modules():
    src = "import time\nnow = time.time()\n"
    assert lint_source(src, "dragonboat_tpu/metrics.py") == []
    assert rules_of(lint_source(src, "dragonboat_tpu/faults.py")) == {
        "determinism"
    }


# ---------------------------------------------------------------------------
# width-64
# ---------------------------------------------------------------------------
def test_width64_catches_unmasked_q_pack():
    src = '''
import struct
_u64 = struct.Struct("<Q")

def encode(v):
    return _u64.pack(v)
'''
    fs = lint_source(src, "dragonboat_tpu/transport/wire.py")
    assert rules_of(fs) == {"width-64"}


def test_width64_accepts_masked_len_and_literals():
    src = '''
import struct
from ..pb import MASK64
_u64 = struct.Struct("<Q")

def encode(b, v, blob):
    b.write(_u64.pack(v & MASK64))
    b.write(struct.pack("<Q", len(blob)))
    b.write(struct.pack("<QQ", 7, v & 0xFFFFFFFFFFFFFFFF))
'''
    assert lint_source(src, "dragonboat_tpu/transport/wire.py") == []


def test_width64_maps_q_slots_in_mixed_formats():
    src = '''
import struct
_hdr = struct.Struct(">BQQ")

def key(kind, shard, replica):
    return _hdr.pack(kind, shard, replica)
'''
    fs = lint_source(src, "dragonboat_tpu/storage/kvlogdb.py")
    # the B slot (kind) is exempt; both Q slots flagged
    assert len(fs) == 2 and rules_of(fs) == {"width-64"}


# ---------------------------------------------------------------------------
# gateway-hot (the serving front plane's lock-free read-path rule)
# ---------------------------------------------------------------------------
GATEWAY_HOT_SRC = '''
import threading

class RoutingCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._table = {}

    def lookup(self, shard_id):  # gateway-hot
        with self._lock:
            return self._table.get(shard_id)

    def probe(self, shard_id):  # gateway-hot
        self._lock.acquire()
        try:
            return self._table.get(shard_id)
        finally:
            self._lock.release()

    def snapshot_ok(self, shard_id):  # gateway-hot
        return self._table.get(shard_id)

    def learn(self, shard_id, host):
        with self._lock:
            t = dict(self._table)
            t[shard_id] = host
            self._table = t
'''


def test_gateway_hot_catches_locked_read_path():
    fs = lint_source(GATEWAY_HOT_SRC, "dragonboat_tpu/gateway/routing.py")
    assert rules_of(fs) == {"gateway-hot"} and len(fs) == 2
    flagged = [GATEWAY_HOT_SRC.splitlines()[f.line - 1] for f in fs]
    assert any("with self._lock" in ln for ln in flagged), flagged
    assert any(".acquire()" in ln for ln in flagged), flagged


def test_gateway_hot_scoped_to_gateway_modules_and_marked_funcs():
    # write paths (no marker) may lock; other modules are out of scope
    assert lint_source(
        GATEWAY_HOT_SRC, "dragonboat_tpu/balance/view.py"
    ) == []
    unmarked = GATEWAY_HOT_SRC.replace("  # gateway-hot", "")
    assert lint_source(
        unmarked, "dragonboat_tpu/gateway/routing.py"
    ) == []


def test_gateway_hot_point_suppression():
    src = GATEWAY_HOT_SRC.replace(
        "        with self._lock:\n            return self._table.get(shard_id)",
        "        # raftlint: ignore[gateway-hot] cold diagnostic path\n"
        "        with self._lock:\n            return self._table.get(shard_id)",
        1,
    )
    fs = lint_source(src, "dragonboat_tpu/gateway/routing.py")
    assert len(fs) == 1 and rules_of(fs) == {"gateway-hot"}


def test_gateway_hot_real_tree_annotation_is_live():
    """RoutingCache.lookup carries the # gateway-hot marker; a with-lock
    seeded into its body must surface — the real tree's annotation is
    live, not decorative."""
    path = os.path.join(REPO, "dragonboat_tpu/gateway/routing.py")
    with open(path) as f:
        src = f.read()
    assert "# gateway-hot" in src
    needle = '"""Current route, or None.  NO locking: one dict load, one get."""'
    assert needle in src
    seeded = src.replace(
        needle, needle + "\n        with self._lock:\n            pass"
    )
    fs = lint_source(seeded, "dragonboat_tpu/gateway/routing.py")
    assert any(f.rule == "gateway-hot" for f in fs)


# ---------------------------------------------------------------------------
# host-sync (the device-plane modules: ops/kernel.py, ops/route.py)
# ---------------------------------------------------------------------------
HOST_SYNC_SRC = '''
import numpy as np

def handler(st, msg):
    n = int(msg["ent"].shape[0])  # static fact: exempt
    k = len(msg["ids"])  # plain len: no call to flag at all
    cap = int(2**31 - 1)  # literal: exempt
    v = int(st.term)  # device concretization
    f = float(st.committed)  # device concretization
    x = st.committed.item()  # forced sync
    arr = np.asarray(st.ring_term)  # host materialization
    return v, f, x, arr, n, k, cap
'''


def test_host_sync_catches_device_syncs():
    fs = lint_source(HOST_SYNC_SRC, "dragonboat_tpu/ops/kernel.py")
    assert rules_of(fs) == {"host-sync"} and len(fs) == 4
    flagged = [HOST_SYNC_SRC.splitlines()[f.line - 1] for f in fs]
    for needle in ("int(st.term)", "float(st.committed)",
                   ".item()", "np.asarray"):
        assert any(needle in ln for ln in flagged), (needle, flagged)


def test_host_sync_scoped_to_device_modules():
    # engine.py/colocated.py legitimately sync (launch readback lives
    # there); the rule only polices the pure-device modules
    assert lint_source(HOST_SYNC_SRC, "dragonboat_tpu/ops/engine.py") == []
    assert lint_source(HOST_SYNC_SRC, "dragonboat_tpu/node.py") == []


def test_host_sync_def_line_ignore_exempts_function():
    src = HOST_SYNC_SRC.replace(
        "def handler(st, msg):",
        "def handler(st, msg):  # raftlint: ignore[host-sync] host helper",
    )
    assert lint_source(src, "dragonboat_tpu/ops/route.py") == []


def test_host_sync_point_suppression():
    src = HOST_SYNC_SRC.replace(
        'x = st.committed.item()  # forced sync',
        'x = st.committed.item()  # raftlint: ignore[host-sync] staged',
    )
    fs = lint_source(src, "dragonboat_tpu/ops/kernel.py")
    assert len(fs) == 3 and rules_of(fs) == {"host-sync"}


def test_host_sync_real_tree_suppression_is_live():
    """route.py's build_route_tables rides the def-line exemption; if
    the annotation is stripped, its numpy precompute must surface — the
    suppression is real, not vacuous."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/route.py")
    src = open(path).read()
    assert lint_source(src, "dragonboat_tpu/ops/route.py") == []
    stripped = src.replace("# raftlint: ignore[host-sync]", "# stripped")
    fs = lint_source(stripped, "dragonboat_tpu/ops/route.py")
    assert len(fs) >= 5 and rules_of(fs) == {"host-sync"}


# ---------------------------------------------------------------------------
# host-loop (the colocated host plane: ops/colocated.py, ops/hostplane.py)
# ---------------------------------------------------------------------------
HOST_LOOP_SRC = '''
import numpy as np

def build_sets(flags, rows):  # hostplane-hot
    out = []
    for g in rows:
        out.append(flags[g])
    at = {int(g): k for k, g in enumerate(rows)}
    ok = all(g in at for g in rows)
    return out, at, ok

def vectorized(flags, rows):  # hostplane-hot
    pos = np.full((flags.shape[0],), -1, np.int32)
    pos[rows] = np.arange(len(rows), dtype=np.int32)
    return pos

def unmarked_helper(rows):
    return [g for g in rows]

# raftlint: ignore is NOT needed on unmarked functions; the def-line
# form below documents a scalar fallback inside the hot discipline
def oracle(flags, rows):  # hostplane-hot  # raftlint: ignore[host-loop] documented scalar fallback (parity oracle)
    return [flags[g] for g in rows]
'''


def test_host_loop_catches_for_over_rows():
    fs = lint_source(HOST_LOOP_SRC, "dragonboat_tpu/ops/colocated.py")
    # the for loop, the dict comprehension, and the all(...) generator
    assert rules_of(fs) == {"host-loop"} and len(fs) == 3, fs
    flagged = [HOST_LOOP_SRC.splitlines()[f.line - 1] for f in fs]
    assert any("for g in rows:" in ln for ln in flagged), flagged
    assert any("enumerate(rows)" in ln for ln in flagged), flagged
    assert any("all(" in ln for ln in flagged), flagged


def test_host_loop_scoped_to_hostplane_modules_and_marked_funcs():
    # other modules are out of scope; unmarked functions may loop
    assert lint_source(HOST_LOOP_SRC, "dragonboat_tpu/obs/trace.py") == []
    unmarked = HOST_LOOP_SRC.replace("  # hostplane-hot", "")
    assert lint_source(unmarked, "dragonboat_tpu/ops/hostplane.py") == []


def test_host_loop_def_line_ignore_exempts_function():
    # the `oracle` function above loops but carries the def-line ignore
    fs = lint_source(HOST_LOOP_SRC, "dragonboat_tpu/ops/hostplane.py")
    lines = {f.line for f in fs}
    oracle_line = next(
        i + 1
        for i, ln in enumerate(HOST_LOOP_SRC.splitlines())
        if "def oracle" in ln
    )
    assert oracle_line + 1 not in lines


def test_host_loop_ignore_above_def_line_exempts_function():
    """The ignore-next-line style works on defs too (the real tree's
    scalar-oracle comments sit above the def)."""
    src = (
        "# raftlint: ignore[host-loop] documented parity oracle\n"
        "def twin(rows):  # hostplane-hot\n"
        "    return [g for g in rows]\n"
    )
    assert lint_source(src, "dragonboat_tpu/ops/hostplane.py") == []
    stripped = src.replace("# raftlint: ignore[host-loop]", "# nope")
    fs = lint_source(stripped, "dragonboat_tpu/ops/hostplane.py")
    assert rules_of(fs) == {"host-loop"}


def test_host_loop_point_suppression():
    src = HOST_LOOP_SRC.replace(
        "    for g in rows:",
        "    # raftlint: ignore[host-loop] boundary loop: per-node dict lookups\n"
        "    for g in rows:",
        1,
    )
    fs = lint_source(src, "dragonboat_tpu/ops/colocated.py")
    assert len(fs) == 2 and rules_of(fs) == {"host-loop"}


def test_host_loop_real_tree_annotation_is_live():
    """hostplane.build_merge_sets carries the # hostplane-hot marker; a
    for-over-rows seeded into its body must surface — the real tree's
    annotation is live, not decorative."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/hostplane.py")
    src = open(path).read()
    assert "# hostplane-hot" in src
    assert lint_source(src, "dragonboat_tpu/ops/hostplane.py") == []
    needle = "    batch_mask = _mask_of(G, batch_gs)"
    assert needle in src
    seeded = src.replace(
        needle,
        "    junk = [int(f) for f in flags]\n" + needle,
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/hostplane.py")
    assert any(f.rule == "host-loop" for f in fs)


def test_host_loop_real_tree_colocated_annotation_is_live():
    """The colocated _sel_cover coverage check is annotated; seeding a
    per-row membership scan into it must surface."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/colocated.py")
    src = open(path).read()
    needle = "        rows_buf, rows_slot, rows_need, rows_append, rows_sum = sel_rows"
    assert needle in src
    seeded = src.replace(
        needle,
        needle + "\n        junk = {int(g): 1 for g in rows_buf}",
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/colocated.py")
    assert any(f.rule == "host-loop" for f in fs)


def test_host_loop_engine_module_in_scope():
    """ops/engine.py joined HOSTPLANE_MODULES for the ISSUE-13 lane
    machinery: marked functions there are held to the same no-loop
    discipline as hostplane/colocated."""
    fs = lint_source(HOST_LOOP_SRC, "dragonboat_tpu/ops/engine.py")
    assert rules_of(fs) == {"host-loop"} and len(fs) == 3, fs


def test_host_loop_real_tree_lane_plan_annotation_is_live():
    """plan_update_sync (the r9 update-lane classifier) carries the
    # hostplane-hot marker; a for-over-rows seeded into its body must
    surface."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/hostplane.py")
    src = open(path).read()
    assert "def plan_update_sync(  # hostplane-hot" in src
    assert lint_source(src, "dragonboat_tpu/ops/hostplane.py") == []
    needle = "    in_sum = sum_k >= 0"
    assert needle in src
    seeded = src.replace(
        needle,
        "    junk = [int(k) for k in sum_k]\n" + needle,
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/hostplane.py")
    assert any(f.rule == "host-loop" for f in fs)


def test_host_loop_real_tree_tick_lane_annotation_is_live():
    """encode_tick_lane (PR 27: a launch's tick-only rows as two arrays)
    carries the # hostplane-hot marker; a per-row loop seeded into its
    body must surface, so the ~2,000 rows a launch it was written to
    keep out of Python cannot grow back in."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/hostplane.py")
    src = open(path).read()
    assert "def encode_tick_lane(  # hostplane-hot" in src
    assert lint_source(src, "dragonboat_tpu/ops/hostplane.py") == []
    needle = "    tick_counts = np.zeros((G,), np.int32)\n    if len(tick_gs):"
    assert needle in src
    seeded = src.replace(
        needle,
        "    for g, n in zip(tick_gs, tick_n):\n        pass\n" + needle,
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/hostplane.py")
    assert [f.rule for f in fs] == ["host-loop"], fs


def test_host_loop_real_tree_completion_lane_annotations_are_live():
    """The array passes PR 29 put under the completion (the lease step
    over a whole launch — since PR 31 the launch form's,
    ``LeaseAges.lanes_step`` — the lane's columns, the rows a completion
    leaves alone, the wake) carry the # hostplane-hot marker; a per-row
    loop seeded into each must surface, so the ~2,000-3,300 tick-only
    rows a launch they keep out of Python cannot grow back in."""
    seeds = {
        "dragonboat_tpu/ops/hostplane.py": [
            ("    def lanes_step(  # hostplane-hot",
             "        ticked = (self.et[gs] > 0) & (fed > 0)\n",
             "        for g in gs:\n            pass\n"),
            ("    def seal(self) -> \"TickLane\":  # hostplane-hot",
             "        self.gs_np = np.asarray(self.gs, np.int64)\n",
             "        junk = [int(g) for g in self.gs]\n"),
        ],
        "dragonboat_tpu/ops/colocated.py": [
            ("    def _lease_pass(  # hostplane-hot",
             "        lease = self._lease\n        gs_step = gs[:n_step]\n",
             "        for node in nodes:\n            pass\n"),
            ("    def _skip_mask(  # hostplane-hot",
             "        skip = ~self._lanes.attached[gs]\n",
             "        junk = [n.stopped for n in nodes]\n"),
            ("    def _wake_alive(self) -> None:  # hostplane-hot",
             "        alive = self._lanes.alive_mask()\n        slot = ",
             "        for meta in self._meta.values():\n            pass\n"),
        ],
    }
    for rel, cases in seeds.items():
        src = open(os.path.join(REPO, rel)).read()
        assert lint_source(src, rel) == []
        for def_line, needle, junk in cases:
            assert def_line in src, def_line
            assert src.count(needle) == 1, needle
            fs = lint_source(src.replace(needle, junk + needle, 1), rel)
            assert [f.rule for f in fs] == ["host-loop"], (def_line, fs)
        # the documented residue loop (the rows whose role changed: the
        # window form's two others went with it, PR 31) is exempt by
        # its point ignore, and only by it
        stripped = src.replace(
            "# raftlint: ignore[host-loop] residue:", "# stripped:")
        if stripped != src:
            fs = lint_source(stripped, rel)
            assert len(fs) == 1 and {f.rule for f in fs} == {"host-loop"}


def test_host_loop_lane_scalar_oracle_ignore_is_live():
    """plan_update_sync_scalar (the documented per-row parity oracle)
    is exempted by a def-line-adjacent ignore; stripping the ignore
    must surface its row loop — the exemption is doing real work."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/hostplane.py")
    src = open(path).read()
    marker = "# raftlint: ignore[host-loop] parity oracle"
    assert marker in src
    stripped = src.replace(marker, "# stripped", 1)
    fs = lint_source(stripped, "dragonboat_tpu/ops/hostplane.py")
    assert any(f.rule == "host-loop" for f in fs), (
        "stripping the scalar-oracle ignore surfaced nothing — either "
        "the oracle lost its hot marker or the rule went dead"
    )


def test_host_loop_real_tree_engine_lane_assembly_is_live():
    """_plan_lane_words (ops/engine.py's lane assembly) is marked; a
    per-row scan seeded into it must surface — the engine module's
    membership in HOSTPLANE_MODULES is live, not decorative."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/engine.py")
    src = open(path).read()
    assert "def _plan_lane_words(  # hostplane-hot" in src
    assert lint_source(src, "dragonboat_tpu/ops/engine.py") == []
    needle = "    old_w = ulanes.words[:, gs_live]"
    assert needle in src
    seeded = src.replace(
        needle,
        "    junk = [int(g) for g in gs_live]\n" + needle,
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/engine.py")
    assert any(f.rule == "host-loop" for f in fs)


# ---------------------------------------------------------------------------
# sync-budget (# sync-hot launch-pipeline functions: one readback per
# generation)
# ---------------------------------------------------------------------------
SYNC_BUDGET_SRC = '''
import numpy as np
import jax

def _complete(dev, vals):  # sync-hot
    a = np.asarray(dev)            # bare readback: flagged
    b = jax.device_get(dev)        # flagged
    c = dev.item()                 # flagged
    return a, b, c

def _unmarked(dev):
    return np.asarray(dev)         # unmarked functions are free

def _sanctioned(dev):  # sync-hot
    # raftlint: ignore[sync-budget] the launch blob readback
    head = np.asarray(dev)
    return head
'''


def test_sync_budget_catches_bare_syncs():
    fs = lint_source(SYNC_BUDGET_SRC, "dragonboat_tpu/ops/colocated.py")
    assert rules_of(fs) == {"sync-budget"} and len(fs) == 3, fs
    flagged = [SYNC_BUDGET_SRC.splitlines()[f.line - 1] for f in fs]
    assert any("np.asarray(dev)" in ln and "bare" in ln for ln in flagged)
    assert any("device_get" in ln for ln in flagged), flagged
    assert any(".item()" in ln for ln in flagged), flagged


def test_sync_budget_scoped_to_launch_modules_and_marked_funcs():
    # other modules are out of scope; unmarked functions may sync
    assert lint_source(SYNC_BUDGET_SRC, "dragonboat_tpu/obs/trace.py") == []
    unmarked = SYNC_BUDGET_SRC.replace("  # sync-hot", "")
    assert lint_source(unmarked, "dragonboat_tpu/ops/colocated.py") == []
    # engine.py is in scope too (the fallback gather path lives there)
    fs = lint_source(SYNC_BUDGET_SRC, "dragonboat_tpu/ops/engine.py")
    assert rules_of(fs) == {"sync-budget"} and len(fs) == 3


def test_sync_budget_point_ignore_sanctions_the_blob_readback():
    # _sanctioned's annotated collect raises nothing; stripping the
    # annotation must surface it (the ignore is live)
    stripped = SYNC_BUDGET_SRC.replace(
        "# raftlint: ignore[sync-budget]", "# nope"
    )
    fs = lint_source(stripped, "dragonboat_tpu/ops/colocated.py")
    assert len(fs) == 4, fs


def test_sync_budget_real_tree_annotation_is_live():
    """The real colocated launch path is marked # sync-hot and lints
    clean; stripping its point ignores must surface the blob collect —
    the annotation is load-bearing, not decorative."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/colocated.py")
    src = open(path).read()
    assert "# sync-hot" in src
    assert lint_source(src, "dragonboat_tpu/ops/colocated.py") == []
    stripped = src.replace("# raftlint: ignore[sync-budget]", "# stripped")
    fs = lint_source(stripped, "dragonboat_tpu/ops/colocated.py")
    assert any(f.rule == "sync-budget" for f in fs), (
        "stripping the sanctioned-readback ignores surfaced nothing"
    )


def test_sync_budget_real_tree_seeded_sync_is_caught():
    """Seeding a stray device_get into the marked completion path must
    surface — each stray sync is one more host round trip that
    defeats the pipeline."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/colocated.py")
    src = open(path).read()
    needle = "        flags = head[:G]"
    assert needle in src
    seeded = src.replace(
        needle,
        "        junk = jax.device_get(rec.head_dev)\n" + needle,
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/colocated.py")
    assert any(f.rule == "sync-budget" for f in fs)


# fused commit waves (ISSUE 15): a K-round wave's budget is still ONE
# sanctioned readback window — a stray sync BETWEEN fused rounds pays
# a fresh readback latency per wave and silently reverts the wave to
# the 3-readback commit it exists to kill.
FUSED_WAVE_SRC = '''
import numpy as np

def _launch_wave(state, pending, rounds):  # sync-hot
    for _k in range(rounds):
        state, out = _step(state, pending)
        pending = _route(state, out)
    return state, pending

def _launch_wave_with_stray_sync(state, pending, rounds):  # sync-hot
    for _k in range(rounds):
        state, out = _step(state, pending)
        probe = np.asarray(out)        # stray mid-wave sync: flagged
        pending = _route(state, out)
    return state, pending

def _complete_wave(heads, t_req):  # sync-hot
    out = []
    for dev in heads:
        # raftlint: ignore[sync-budget] the wave's sanctioned collect
        out.append(np.asarray(dev))
    return out
'''


def test_sync_budget_fused_wave_with_stray_sync_fails():
    """The fused-wave shape: a clean K-round dispatch loop lints green,
    the same loop with a mid-wave sync is flagged, and the wave's ONE
    sanctioned collect (point-ignored) passes."""
    fs = lint_source(FUSED_WAVE_SRC, "dragonboat_tpu/ops/colocated.py")
    assert rules_of(fs) == {"sync-budget"} and len(fs) == 1, fs
    line = FUSED_WAVE_SRC.splitlines()[fs[0].line - 1]
    assert "stray mid-wave sync" in line, line
    # stripping the sanctioned collect's ignore surfaces it too
    stripped = FUSED_WAVE_SRC.replace("# raftlint: ignore[sync-budget]",
                                      "# nope")
    fs2 = lint_source(stripped, "dragonboat_tpu/ops/colocated.py")
    assert len(fs2) == 2, fs2


def test_sync_budget_real_fused_round_loop_is_marked():
    """The real fused-wave dispatch loop and round-major merge carry
    the # sync-hot discipline: the functions exist, are marked, and
    seeding a stray sync between dispatched rounds is caught."""
    path = os.path.join(REPO, "dragonboat_tpu/ops/colocated.py")
    src = open(path).read()
    assert "def _merge_intermediate_round(  # sync-hot" in src
    needle = "                for _k in range(1, rounds):"
    assert needle in src
    seeded = src.replace(
        needle,
        "                junk = jax.device_get(merged_l[0])\n" + needle,
        1,
    )
    fs = lint_source(seeded, "dragonboat_tpu/ops/colocated.py")
    assert any(f.rule == "sync-budget" for f in fs), (
        "a stray sync between fused rounds went unflagged"
    )


# ---------------------------------------------------------------------------
# hygiene: import-hot, bare-except, thread-discipline
# ---------------------------------------------------------------------------
def test_import_hot_flags_function_level_imports_in_hot_modules():
    src = "def apply():\n    from .raftio import NodeInfoEvent\n    return 1\n"
    assert rules_of(lint_source(src, "dragonboat_tpu/node.py")) == {
        "import-hot"
    }
    assert rules_of(lint_source(src, "dragonboat_tpu/engine/execengine.py")) == {
        "import-hot"
    }
    # cold modules may lazy-import (circularity breaks etc.)
    assert lint_source(src, "dragonboat_tpu/tools.py") == []


def test_bare_except_flagged_everywhere():
    src = "try:\n    x = 1\nexcept:\n    pass\n"
    assert rules_of(lint_source(src, "dragonboat_tpu/anything.py")) == {
        "bare-except"
    }


def test_thread_discipline_requires_name_and_daemon():
    src = '''
import threading
t = threading.Thread(target=print)
u = threading.Thread(target=print, name="ok", daemon=True)
'''
    fs = lint_source(src, "dragonboat_tpu/x.py")
    assert len(fs) == 2  # missing name AND missing daemon, once each
    assert rules_of(fs) == {"thread-discipline"}


# ---------------------------------------------------------------------------
# baseline machinery + the tree gate
# ---------------------------------------------------------------------------
def test_baseline_roundtrip_and_gate(tmp_path):
    fs = [
        Finding("a.py", 3, "bare-except", "m"),
        Finding("a.py", 9, "bare-except", "m"),
        Finding("b.py", 1, "width-64", "m"),
    ]
    p = tmp_path / "baseline.txt"
    write_baseline(str(p), fs)
    bl = load_baseline(str(p))
    assert bl == {("a.py", "bare-except"): 2, ("b.py", "width-64"): 1}
    # covered exactly -> no new findings
    new, stale = gate(fs, bl)
    assert new == [] and stale == []
    # one more finding in a covered file -> the whole group is reported
    new, _ = gate(fs + [Finding("a.py", 20, "bare-except", "m")], bl)
    assert len(new) == 3 and all(f.path == "a.py" for f in new)
    # debt shrank -> stale note for the ratchet
    new, stale = gate(fs[1:], bl)
    assert new == [] and stale == [("a.py", "bare-except", 2, 1)]


def test_baseline_rejects_malformed_lines(tmp_path):
    p = tmp_path / "bad.txt"
    p.write_text("a.py bare-except\n")
    with pytest.raises(ValueError):
        load_baseline(str(p))


def test_tree_is_lint_clean_with_checked_in_baseline():
    """THE gate, same invocation as scripts/lint.sh: zero unbaselined
    findings over the package."""
    old = os.getcwd()
    os.chdir(REPO)
    try:
        findings = lint_paths(["dragonboat_tpu"])
        baseline = load_baseline(
            os.path.join(REPO, "dragonboat_tpu/analysis/baseline.txt")
        )
        new, _ = gate(findings, baseline)
    finally:
        os.chdir(old)
    assert new == [], "\n".join(f.render() for f in new)


def test_real_tree_annotations_are_live():
    """The seed guarded-by annotations actually register (the rule must
    not be passing vacuously): stripping node.py's inline ignores must
    surface the documented lock-free reads as findings."""
    path = os.path.join(REPO, "dragonboat_tpu/node.py")
    src = open(path).read()
    assert lint_source(src, "dragonboat_tpu/node.py") == []
    stripped = src.replace("# raftlint: ignore[guarded-by]", "# stripped")
    fs = lint_source(stripped, "dragonboat_tpu/node.py")
    assert len(fs) >= 8 and rules_of(fs) == {"guarded-by"}


# ---------------------------------------------------------------------------
# lockcheck: the dynamic witness
# ---------------------------------------------------------------------------
@pytest.fixture
def witness():
    w = lockcheck.install(slow_wait_s=0.2)
    try:
        yield w
    finally:
        lockcheck.uninstall()


def test_lockcheck_detects_inverted_two_lock_acquisition(witness):
    """Deliberate ABBA: thread 1 takes A->B, thread 2 takes B->A.  The
    witness must report a cycle with BOTH witness stacks even though the
    schedule below never actually deadlocks."""
    A = witness.make_lock("fixture:A")
    B = witness.make_lock("fixture:B")
    done = threading.Barrier(2, timeout=5)

    def t1():
        with A:
            with B:
                pass
        done.wait()

    def t2():
        done.wait()  # strictly after t1: records B->A without deadlocking
        with B:
            with A:
                pass

    th1 = threading.Thread(target=t1, name="abba-1", daemon=True)
    th2 = threading.Thread(target=t2, name="abba-2", daemon=True)
    th1.start(); th2.start(); th1.join(5); th2.join(5)
    r = witness.report()
    assert len(r["cycles"]) == 1
    cyc = r["cycles"][0]
    assert len(cyc["edges"]) == 2  # both directions, each with its stack
    for e in cyc["edges"]:
        assert e["stack"], "witness stack missing"
    text = witness.format_cycles()
    assert "fixture:A" in text and "fixture:B" in text
    with pytest.raises(lockcheck.LockOrderViolation):
        witness.assert_clean()


def test_lockcheck_consistent_order_is_clean(witness):
    A = witness.make_lock("c:A")
    B = witness.make_lock("c:B")

    def worker():
        for _ in range(50):
            with A:
                with B:
                    pass

    ts = [threading.Thread(target=worker, name=f"c{i}", daemon=True)
          for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5)
    witness.assert_clean()
    assert witness.report()["edges"] == 1  # A->B only, recorded once


def test_lockcheck_rlock_reentrancy_no_self_edge(witness):
    R = witness.make_lock("r:R", reentrant=True)
    with R:
        with R:  # re-entry must not create an R->R edge or a cycle
            pass
    witness.assert_clean()
    assert witness.report()["edges"] == 0


def test_lockcheck_flags_slow_wait_while_holding_another_lock(witness):
    A = witness.make_lock("s:A")
    B = witness.make_lock("s:B")
    release = threading.Event()

    def holder():
        with B:
            release.wait(2)

    th = threading.Thread(target=holder, name="holder", daemon=True)
    th.start()
    time.sleep(0.05)  # let holder take B
    with A:  # waiting for B while holding A -> flagged past slow_wait_s
        t = threading.Timer(0.4, release.set)
        t.start()
        with B:
            pass
    th.join(5)
    waits = witness.report()["slow_waits"]
    assert len(waits) == 1
    assert waits[0]["lock"] == "s:B" and waits[0]["held"] == ["s:A"]
    assert waits[0]["waited_s"] >= 0.2
    witness.assert_clean()  # a slow wait is a flag, not a cycle


def test_lockcheck_tracks_project_locks_and_restores_threading():
    assert threading.Lock is lockcheck._REAL_LOCK
    w = lockcheck.install()
    try:
        from dragonboat_tpu.metrics import MetricsRegistry

        reg = MetricsRegistry()
        assert type(reg._lock).__name__ == "_TrackedLock"
        # stdlib-created locks stay real (zero overhead off the project)
        import queue

        q = queue.Queue()
        assert type(q.mutex).__name__ != "_TrackedLock"
    finally:
        lockcheck.uninstall()
    assert threading.Lock is lockcheck._REAL_LOCK
    # locks created while tracked keep working after uninstall
    with reg._lock:
        pass


def test_lockcheck_condition_wait_releases_held_stack(witness):
    """Condition(tracked_lock).wait must fully release the lock in the
    witness's view — a waiter must NOT appear to hold it (phantom edges
    would poison the graph with false cycles)."""
    L = witness.make_lock("cv:L")
    cv = threading.Condition(L)
    other = witness.make_lock("cv:other")
    woke = []

    def waiter():
        with cv:
            cv.wait(timeout=2)
            woke.append(True)

    th = threading.Thread(target=waiter, name="cv-waiter", daemon=True)
    th.start()
    time.sleep(0.1)
    # while the waiter sleeps inside wait(), take other->L: if wait had
    # left L on the waiter's stack this would still be fine (different
    # thread), but the notify path below re-acquires without edges
    with other:
        with cv:
            cv.notify()
    th.join(5)
    assert woke == [True]
    witness.assert_clean()


def test_lockcheck_env_gate_matches_invariants_pattern():
    assert hasattr(lockcheck, "ENABLED")
    old = lockcheck.ENABLED
    try:
        lockcheck.enable(False)
        assert lockcheck.ENABLED is False
        lockcheck.enable(True)
        assert lockcheck.ENABLED is True
    finally:
        lockcheck.enable(old)


# ---------------------------------------------------------------------------
# stream-read (the big-state streaming path: bounded reads only)
# ---------------------------------------------------------------------------
STREAM_READ_SRC = '''
def reassemble(f):
    return f.read()


def copy(src, dst):
    while True:
        piece = src.read(1 << 20)
        if not piece:
            break
        dst.write(piece)


def meta(f):
    # raftlint: ignore[stream-read] bounded metadata blob
    return f.read()
'''


def test_stream_read_flags_unbounded_read_in_stream_modules():
    for mod in (
        "dragonboat_tpu/transport/chunk.py",
        "dragonboat_tpu/storage/snapshotter.py",
        "dragonboat_tpu/bigstate/dr.py",
        "dragonboat_tpu/tools.py",
    ):
        fs = lint_source(STREAM_READ_SRC, mod)
        # reassemble() flagged; copy()'s sized read and the annotated
        # meta() read pass
        assert rules_of(fs) == {"stream-read"} and len(fs) == 1, (mod, fs)


def test_stream_read_scoped_to_stream_modules():
    assert lint_source(STREAM_READ_SRC, "dragonboat_tpu/gateway/x.py") == []


def test_stream_read_ignore_annotation_is_live():
    stripped = STREAM_READ_SRC.replace(
        "# raftlint: ignore[stream-read]", "# stripped"
    )
    fs = lint_source(stripped, "dragonboat_tpu/bigstate/dr.py")
    assert len(fs) == 2 and rules_of(fs) == {"stream-read"}


# ---------------------------------------------------------------------------
# obs-bound (the fleet-scope obs plane: every ring slice is bounded)
# ---------------------------------------------------------------------------
OBS_BOUND_SRC = '''
def answer(rec, tracer, svc, cursor):
    a = rec.tail(cursor)
    b = tracer.finished_tail(cursor)
    c = svc.recorder_tail(cursor, limit=256)
    d = svc.trace_spans(cursor, limit=64)
    return a, b, c, d


def drain(rec, cursor):
    # raftlint: ignore[obs-bound] local dump path, never crosses the wire
    return rec.tail(cursor)
'''


def test_obs_bound_flags_unlimited_tails_in_obs_modules():
    for mod in (
        "dragonboat_tpu/obs/fleetscope.py",
        "dragonboat_tpu/gateway/rpc.py",
    ):
        fs = lint_source(OBS_BOUND_SRC, mod)
        # the two limit-less slices flagged; the explicit limit= calls
        # and the annotated drain() pass
        assert rules_of(fs) == {"obs-bound"} and len(fs) == 2, (mod, fs)


def test_obs_bound_scoped_to_obs_reply_modules():
    assert lint_source(OBS_BOUND_SRC, "dragonboat_tpu/obs/recorder.py") == []
    assert lint_source(OBS_BOUND_SRC, "dragonboat_tpu/nodehost.py") == []


def test_obs_bound_ignore_annotation_is_live():
    stripped = OBS_BOUND_SRC.replace(
        "# raftlint: ignore[obs-bound]", "# stripped"
    )
    fs = lint_source(stripped, "dragonboat_tpu/obs/fleetscope.py")
    assert len(fs) == 3 and rules_of(fs) == {"obs-bound"}


def test_obs_bound_repo_is_clean():
    # the real obs plane must itself obey the rule it ships
    for rel in raftlint.OBS_REPLY_MODULES:
        with open(os.path.join(REPO, rel)) as f:
            fs = lint_source(f.read(), rel)
        assert not [x for x in fs if x.rule == "obs-bound"], (rel, fs)


# ---------------------------------------------------------------------------
# wirecheck: the wire-plane auditor (codec registry, goldens, skew
# matrix, deterministic fuzz, rot guards) — true-positive fixtures per
# rule + the zero-unbaselined-tree gate, mirroring the raftlint section
# ---------------------------------------------------------------------------
import struct as _struct

from dragonboat_tpu.analysis import wire_registry, wirecheck
from dragonboat_tpu.analysis.wire_registry import CodecEntry
from dragonboat_tpu.analysis.wirecheck import (
    check_decode_bounds_source,
    check_fuzz,
    check_goldens,
    check_skew,
    golden_name,
    scan_module_source,
)


def _entry(**kw):
    base = dict(
        name="fx",
        module="fx.py",
        samples={"v0": lambda: _struct.pack("<QQQ", 1, 2, 3)},
        decode=lambda d: _struct.unpack("<QQQ", d),
        errors=(ValueError,),
    )
    base.update(kw)
    return CodecEntry(**base)


class TestWirecheckGoldens:
    def test_mutated_golden_is_named_frame_failure(self, tmp_path):
        e = wire_registry.entry("config_change")
        gdir = str(tmp_path)
        check_goldens([e], gdir, update=True)
        assert check_goldens([e], gdir) == []  # fresh corpus: clean
        path = tmp_path / golden_name("config_change", "v0")
        blob = bytearray(path.read_bytes())
        blob[0] ^= 0xFF
        path.write_bytes(bytes(blob))
        fs = [f for f in check_goldens([e], gdir)]
        assert [f.rule for f in fs] == ["golden-drift"]
        assert "config_change" in fs[0].message  # NAMES the frame
        assert golden_name("config_change", "v0") in fs[0].path

    def test_missing_golden_reported(self, tmp_path):
        e = wire_registry.entry("config_change")
        fs = check_goldens([e], str(tmp_path))
        assert {f.rule for f in fs} == {"golden-missing"}


class TestWirecheckSkew:
    def test_future_frame_decoding_silently_is_flagged(self, tmp_path):
        # a decoder that ACCEPTS a future frame = silent field shift
        e = _entry(decode=lambda d: 1, future=lambda: b"\xff" * 24)
        fs = check_skew([e], str(tmp_path))
        assert any(
            f.rule == "skew-matrix" and "DECODED" in f.message for f in fs
        )

    def test_future_frame_broad_error_is_flagged(self, tmp_path):
        def boom(d):
            raise KeyError("nope")  # not the narrow type

        e = _entry(decode=boom, future=lambda: b"\xff" * 24)
        fs = check_skew([e], str(tmp_path))
        assert any(
            f.rule == "skew-matrix" and "narrow error" in f.message
            for f in fs
        )

    def test_real_registry_skew_matrix_is_clean(self):
        assert check_skew(list(wire_registry.REGISTRY),
                          wirecheck.GOLDENS_DIR) == []


class TestWirecheckFuzz:
    def test_bare_struct_error_escape_caught(self, tmp_path):
        fs = check_fuzz([_entry()], str(tmp_path), n=50)
        assert any(f.rule == "fuzz-escape" and "struct" in f.message.lower()
                   for f in fs)

    def test_unbounded_allocation_caught(self, tmp_path):
        e = _entry(
            samples={"v0": lambda: b"\x00" * 8},
            decode=lambda d: bytes(8 * 1024 * 1024),
        )
        fs = check_fuzz([e], str(tmp_path), n=5)
        assert [f.rule for f in fs] == ["fuzz-alloc"]

    def test_narrow_errors_pass(self, tmp_path):
        def dec(d):
            if len(d) != 24:
                raise ValueError("bad length")
            return _struct.unpack("<QQQ", d)

        assert check_fuzz([_entry(decode=dec)], str(tmp_path), n=50) == []

    def test_fuzz_is_deterministic(self, tmp_path):
        runs = [check_fuzz([_entry()], str(tmp_path), n=30)
                for _ in range(2)]
        assert runs[0] == runs[1]  # same seed -> same first escape


class TestWirecheckRotGuards:
    FIXTURE = (
        "KIND_WIDGET = 9\n"
        "WIDGET_BIN_VER = 1\n"
        "def decode_widget(data):\n"
        "    return data\n"
    )

    def test_unregistered_surface_flagged(self):
        fs = scan_module_source(self.FIXTURE, "m.py",
                                claimed=("KIND_WIDGET",))
        assert {f.rule for f in fs} == {"unregistered-codec"}
        flagged = {f.message.split("`")[1] for f in fs}
        assert flagged == {"WIDGET_BIN_VER", "decode_widget"}

    def test_fully_claimed_surface_is_clean(self):
        fs = scan_module_source(
            self.FIXTURE, "m.py",
            claimed=("KIND_WIDGET", "WIDGET_BIN_VER", "decode_widget"),
        )
        assert fs == []

    def test_adding_decoder_to_covered_module_fails_gate(self):
        # the acceptance pin: an unregistered decode_* appended to a
        # REAL covered module must surface as a finding
        rel = "dragonboat_tpu/transport/wire.py"
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        claimed = wire_registry.claimed_names(rel)
        assert scan_module_source(src, rel, claimed) == []
        src += "\ndef decode_widget(data):\n    return data\n"
        fs = scan_module_source(src, rel, claimed)
        assert [f.rule for f in fs] == ["unregistered-codec"]
        assert "decode_widget" in fs[0].message

    def test_decode_bound_stripped_cap_flagged(self):
        src = (
            "import struct\n"
            "def decode_widget(data):\n"
            "    n = struct.unpack(\"<I\", data)[0]\n"
            "    return data.ljust(n)\n"
        )
        fs = check_decode_bounds_source(src, "m.py", ["decode_widget"])
        assert [f.rule for f in fs] == ["decode-bound"]

    def test_decode_bound_bare_zlib_flagged(self):
        src = (
            "import zlib\n"
            "MAX_W = 10\n"
            "def decode_widget(data):\n"
            "    if len(data) > MAX_W:\n"
            "        raise ValueError\n"
            "    return zlib.decompress(data)\n"
        )
        fs = check_decode_bounds_source(src, "m.py", ["decode_widget"])
        assert [f.rule for f in fs] == ["decode-bound"]
        assert "zlib.decompress" in fs[0].message

    def test_decode_bound_capped_decoder_clean(self):
        src = (
            "import struct\n"
            "MAX_W = 10\n"
            "def decode_widget(data):\n"
            "    n = struct.unpack(\"<I\", data)[0]\n"
            "    if n > MAX_W:\n"
            "        raise ValueError\n"
            "    return data.ljust(n)\n"
        )
        assert check_decode_bounds_source(
            src, "m.py", ["decode_widget"]
        ) == []

    def test_missing_registered_decoder_flagged(self):
        fs = check_decode_bounds_source("x = 1\n", "m.py", ["decode_gone"])
        assert [f.rule for f in fs] == ["decode-bound"]
        assert "not found" in fs[0].message


def test_wire_baseline_ratchet_rides_raftlint_machinery(tmp_path):
    fs = [Finding("fx.py", 1, "fuzz-escape", "m")]
    p = tmp_path / "wb.txt"
    write_baseline(str(p), fs)
    new, stale = gate(fs, load_baseline(str(p)))
    assert new == [] and stale == []
    new, _ = gate(fs + [Finding("fx.py", 2, "fuzz-escape", "m")],
                  load_baseline(str(p)))
    assert len(new) == 2


def test_wire_tree_gate_is_clean_with_checked_in_baseline():
    """THE wire gate, same shape as scripts/lint.sh: zero unbaselined
    findings over the full registry (goldens + skew + fuzz + rot
    guards) with the checked-in (EMPTY) wire_baseline.txt."""
    findings = wirecheck.audit(fuzz_n=40)
    baseline = load_baseline(
        os.path.join(REPO, "dragonboat_tpu/analysis/wire_baseline.txt")
    )
    new, _ = gate(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)
