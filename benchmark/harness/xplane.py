"""From the profiler's ``.xplane.pb`` to busy time, programs and gaps.

Read with ``jax.profiler.ProfileData`` alone.  A TPU appears as a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per operation
and whose line ``XLA Modules`` one event per program run; the host's
threads are lines of ``/host:CPU``, and a ``TraceAnnotation`` of the
program is an event there under its own name.  All on one clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# on the CPU backend (rehearsals only) programs run on these host threads
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _intervals(line) -> list:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events if e.duration_ns > 0]


def _union(intervals) -> list:
    """Merged, sorted [start, end) spans."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _program(name: str) -> str:
    """``jit__assemble_and_step(1234567)`` -> ``jit__assemble_and_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_xplane(path: str, labels=()) -> dict:
    """``window_s``, ``busy_s`` (mean over the devices seen), ``programs``
    {name: [runs, seconds]}, ``device_ops`` and ``idle_gaps`` (lists of
    [name, seconds], longest first, at most 10)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host_marks, lo, hi = [], [], None, None
    cpu_fallback = []
    for plane in pd.planes:
        # several host threads share one line name: never key by it
        if DEVICE_PLANE.match(plane.name):
            ops = [x for ln in plane.lines if ln.name == OPS_LINE
                   for x in _intervals(ln)]
            mods = [x for ln in plane.lines if ln.name == MODULES_LINE
                    for x in _intervals(ln)]
            devices.append((ops or mods, mods))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                iv = _intervals(ln)
                host_marks += [x for x in iv if x[2] in labels]
                if ln.name.startswith(CPU_CLIENT_LINE):
                    cpu_fallback += [x for x in iv
                                     if not x[2].startswith("Threadpool")]
                for s, e, _n in iv:
                    lo = s if lo is None or s < lo else lo
                    hi = e if hi is None or e > hi else hi
    if not devices and cpu_fallback:   # CPU rehearsal: no device plane
        devices.append((cpu_fallback, cpu_fallback))
    if not devices:
        raise ValueError(f"{path}: no device plane and no operation")
    for ops, _m in devices:
        for s, e, _n in ops:
            lo = s if lo is None or s < lo else lo
            hi = e if hi is None or e > hi else hi

    busy_ns, programs, gaps = 0.0, {}, []
    for ops, mods in devices:
        spans = _union(ops)
        busy_ns += sum(e - s for s, e in spans)
        for s, e, name in mods:
            p = programs.setdefault(_program(name), [0, 0.0])
            p[0] += 1
            p[1] += (e - s) / 1e9
        edges = [lo] + [x for span in spans for x in span] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    marks = sorted(host_marks)
    by_label, singles = {}, []
    for g0, g1 in gaps:
        cover = {}
        for s, e, name in marks:
            if s >= g1:
                break
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        label = max(cover, key=cover.get) if cover else "none"
        sec = (g1 - g0) / 1e9 / len(devices)
        by_label[label] = by_label.get(label, 0.0) + sec
        singles.append((sec, label))
    singles.sort(reverse=True)
    idle = sorted(([f"all:{k}", v] for k, v in by_label.items()),
                  key=lambda x: -x[1])
    idle += [[f"gap{i}:{lab}", sec]
             for i, (sec, lab) in enumerate(singles[:10 - len(idle)])]
    dev_ops = sorted(([k, v[1] / len(devices)] for k, v in programs.items()),
                     key=lambda x: -x[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / len(devices),
        "devices": len(devices),
        "programs": programs,
        "device_ops": dev_ops,
        "idle_gaps": idle[:10],
    }
