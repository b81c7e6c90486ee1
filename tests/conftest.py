"""Test configuration.

JAX tests run on the CPU backend with XLA forced to expose 8 host
devices: tests/test_multichip.py builds meshes over them, everything
else runs on device 0.  The full NodeHost stack on a mesh is covered by
`__graft_entry__.py`'s dry run, and on real chips by
`chip_smoke.py --chips 4`, not by pytest.
"""
import os
import sys

import pytest

# run the whole suite with internal invariant assertions ON (reference:
# build-tag-gated internal/invariants checks enabled in CI builds [U])
os.environ.setdefault("DRAGONBOAT_TPU_INVARIANTS", "1")

# run the chaos/fault test modules under the lock-order witness
# (analysis/lockcheck, docs/ANALYSIS.md): any lock-order cycle a chaotic
# schedule merely GRAZES — even if this run got lucky with timing —
# fails the test with both witness stacks.  Same env-gate pattern as
# invariants; set =0 to opt out.  Scoped to the modules that churn
# clusters hardest rather than suite-wide to bound the tier-1 budget.
os.environ.setdefault("DRAGONBOAT_TPU_LOCKCHECK", "1")

# eight forced host devices for the mesh-capable paths; must be in the
# environment before the first backend init
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# DRAGONBOAT_TEST_TPU=1 lets a test run target the real chip (used for
# the recorded scale artifacts: the CPU backend can't launch a 65k-row
# program at election cadence; the product backend can) — everything
# else stays on the virtual 8-device CPU mesh.
if os.environ.get("DRAGONBOAT_TEST_TPU", "0").lower() not in ("1", "true"):
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dragonboat_tpu.ops.placement import configure_compile_cache  # noqa: E402

# cache compiled kernels across test processes (the step kernel is large)
configure_compile_cache(jax)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak/chaos schedules (tier-1 runs -m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "flaky_isolated: load-scheduling-sensitive tests that pass in "
        "isolation (ROADMAP's rotating tier-1 flakes).  A failed run is "
        "retried ONCE after the process quiesces (gc + settle sleep) so "
        "residual load from earlier modules can't rotate tier-1 red; a "
        "real regression still fails both runs.",
    )


def pytest_runtest_protocol(item, nextitem):
    """Serial re-run isolation for @pytest.mark.flaky_isolated (see the
    marker registration above).  The two known carriers — the colocated
    forced-escalation chaos schedule and the colocated quiesce
    fast-lane — each pass in isolation and fail only under CPU
    contention from the surrounding suite (both fail identically on
    the pristine seed tree; ROADMAP 'rotating load flakes').  The
    retry runs after a gc + 1.5s settle window, which is the
    'isolation' those tests actually need: background apply/step
    threads from earlier clusters have drained by then."""
    if item.get_closest_marker("flaky_isolated") is None:
        return None
    import gc
    import time as _time

    from _pytest.runner import runtestprotocol

    item.ihook.pytest_runtest_logstart(
        nodeid=item.nodeid, location=item.location
    )
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        gc.collect()
        _time.sleep(1.5)
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for r in reports:
        item.ihook.pytest_runtest_logreport(report=r)
    item.ihook.pytest_runtest_logfinish(
        nodeid=item.nodeid, location=item.location
    )
    return True


# -- lock-order witness for the chaos/fault modules -----------------------
_LOCKCHECK_MODULES = frozenset(
    ("test_chaos", "test_chaos_extended", "test_chaos_colocated", "test_faults")
)

# -- recompile sentry (analysis/jitcheck) for the engine-driven modules ---
# env-gated via DRAGONBOAT_TPU_JITCHECK: each test starts from a fresh
# trace-cache snapshot (engine _warm() re-marks at construction) and
# fails if any ops/ entry point retraced after warmup — the mid-run
# compile that stalls the launch pipeline for seconds (docs/ANALYSIS.md
# "Device-plane audit")
_JITCHECK_MODULES = frozenset(("test_vector_engine", "test_colocated"))


def _lockcheck_wanted(item) -> bool:
    from dragonboat_tpu.analysis import lockcheck

    mod = getattr(item, "module", None)
    return lockcheck.ENABLED and getattr(mod, "__name__", "") in _LOCKCHECK_MODULES


def _jitcheck_wanted(item) -> bool:
    from dragonboat_tpu.analysis import jitcheck

    mod = getattr(item, "module", None)
    return jitcheck.ENABLED and getattr(mod, "__name__", "") in _JITCHECK_MODULES


def pytest_runtest_setup(item):
    if _lockcheck_wanted(item):
        from dragonboat_tpu.analysis import lockcheck

        item._lockcheck_witness = lockcheck.install()
    if _jitcheck_wanted(item):
        from dragonboat_tpu.analysis import jitcheck

        jitcheck.mark_warm()
        item._jitcheck_armed = True


def pytest_runtest_teardown(item, nextitem):
    import pytest as _pytest

    # lockcheck cleanup FIRST: a jitcheck failure below must not skip
    # uninstall() and leak the patched lock constructors into every
    # later test (latent today — the module sets are disjoint — but a
    # shared module would make the ordering load-bearing)
    w = getattr(item, "_lockcheck_witness", None)
    if w is not None:
        del item._lockcheck_witness
        from dragonboat_tpu.analysis import lockcheck

        lockcheck.uninstall()
        if w.cycles:
            _pytest.fail(
                "lock-order witness: cycle(s) recorded during this test\n"
                + w.format_cycles(),
                pytrace=False,
            )
    if getattr(item, "_jitcheck_armed", False):
        del item._jitcheck_armed
        from dragonboat_tpu.analysis import jitcheck

        rows = jitcheck.retraces()
        if rows:
            _pytest.fail(
                "jitcheck: post-warmup retrace(s) during this test\n"
                + jitcheck.format_retraces(rows),
                pytrace=False,
            )


@pytest.fixture(autouse=True)
def _hostplane_parity_gate():
    """Under ``DRAGONBOAT_TPU_HOSTPLANE_PARITY=1`` (the oracle run of
    the colocated tests: docs/PARITY.md) a test fails if the array
    passes and their per-row twins disagreed anywhere while it ran; the
    engine itself only records a difference and goes on."""
    if os.environ.get("DRAGONBOAT_TPU_HOSTPLANE_PARITY", "") != "1":
        yield
        return
    from dragonboat_tpu.ops import hostplane

    before = hostplane.PARITY_FAILURE_COUNT
    yield
    assert hostplane.PARITY_FAILURE_COUNT == before, (
        hostplane.PARITY_FAILURES[-3:]
    )
