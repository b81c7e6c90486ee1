"""Kernel tracing/profiling hooks (SURVEY §5.1).

The reference's observability is metrics + pprof; the TPU engine's
equivalent is the JAX profiler: device kernel timelines (XLA ops of the
vectorized step / routed round) land in a TensorBoard-loadable trace.

Usage::

    from dragonboat_tpu.profiling import trace, annotate

    with trace("/tmp/raft-trace"):        # whole-cluster run
        ... drive a NodeHost with a vector step engine ...

    with annotate("device-step"):         # named region in the trace
        state, out = kernel.step(state, inbox)

Open the trace dir with TensorBoard's profile plugin (or xprof).
"""
from __future__ import annotations

import contextlib
import sys

_NO_REGION = contextlib.nullcontext()


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a JAX profiler trace (device + host) into ``logdir``.
    Without the Python call tracer: it slows the traced program several
    times over and buries the trace; the host's share is told by the
    :func:`annotate` regions."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named host region on the profiler's own clock, so a device idle
    gap can be put down to what the host was doing in it.  Never imports
    jax: a process that has not loaded it (host-only engines, the
    gateway of a remote fleet) cannot be tracing, and gets a no-op.  With
    jax loaded and no trace running the region costs ~0.5 us."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return _NO_REGION
    return prof.TraceAnnotation(name)
