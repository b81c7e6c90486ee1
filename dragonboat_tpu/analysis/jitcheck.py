"""Env-gated recompile sentry (the dynamic half of the device audit).

jaxcheck traces every ops/ entry point ONCE with canonical shapes —
it cannot see drift that only exists at runtime: a shape that varies
launch-to-launch, a weak-typed scalar leaking into an operand, an
uncommitted array keying a second executable (jax keys compiled
programs on shape/dtype/weak-type/sharding/committed-ness of every
argument).  Each such retrace stalls the launch pipeline for seconds
(r5: commits arrived ~25 s late; PR 21 on four v5e chips: a 17 s
compile inside the first write), so the engines go to great lengths to pre-compile every shape
they will ever use (``VectorStepEngine._warm`` and the colocated
ladder-tier warm).  This module turns that effort into a checked
invariant:

* every engine ``_warm()`` calls :func:`mark_warm` (gated on
  ``ENABLED`` — one attribute load when off), snapshotting each
  registered entry point's jit trace-cache size
  (``fn._cache_size()``);
* :func:`retraces` reports every entry point whose cache GREW since
  the snapshot — i.e. something traced a new program after warmup;
* conftest wraps the engine-driven test modules (test_vector_engine,
  test_colocated) and fails any test that retraced, exactly the
  lockcheck pattern.

The switch is ``DRAGONBOAT_TPU_JITCHECK`` (same env-gate family as
``DRAGONBOAT_TPU_INVARIANTS`` / ``_LOCKCHECK``): off by default, free
when off.  See docs/ANALYSIS.md "Device-plane audit".
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

ENABLED = os.environ.get("DRAGONBOAT_TPU_JITCHECK", "0") not in ("", "0")


def enable(on: bool = True) -> None:
    """Programmatic switch (tests)."""
    global ENABLED
    ENABLED = on


class Sentry:
    """Trace-cache watcher over a (name, jitted fn) list.

    The default instance watches the full ops runtime registry; tests
    construct their own over fixture functions."""

    def __init__(self, entries=None):
        self._entries = entries
        self._snap: Optional[Dict[str, int]] = None

    def entries(self):
        if self._entries is not None:
            return self._entries
        from ..ops import registry  # lazy: breaks the ops<->analysis cycle

        return registry.runtime_entry_points()

    def snapshot(self) -> Dict[str, int]:
        return {name: fn._cache_size() for name, fn in self.entries()}

    def mark(self) -> None:
        """Declare 'warmup is complete as of now'."""
        self._snap = self.snapshot()

    def retraces(self) -> List[Tuple[str, int, int]]:
        """(name, at_mark, now) for entries whose cache grew since the
        last mark; empty when never marked (nothing to compare)."""
        if self._snap is None:
            return []
        now = self.snapshot()
        return [
            (name, before, now[name])
            for name, before in self._snap.items()
            if now.get(name, before) > before
        ]


_DEFAULT = Sentry()


def mark_warm() -> None:
    """Called by the engines at the end of ``_warm()`` (and by the
    conftest wrapper at test setup) — resets the post-warmup baseline."""
    _DEFAULT.mark()


def retraces() -> List[Tuple[str, int, int]]:
    return _DEFAULT.retraces()


def format_retraces(rows) -> str:
    return "\n".join(
        f"  {name}: trace cache {before} -> {now} (post-warmup retrace)"
        for name, before, now in rows
    )
