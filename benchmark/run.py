#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment, waits for every group's leader, runs the
load phase and a generator warm-up (all set-up), measures for
``--seconds``, drains, compares what the window produced with the plain
reference, tears down, and prints diagnostics and then — last, on
standard output — the one JSON object the driver reads.  See
``benchmark/README.md``.

    --dryrun [--shards N]   rehearsal on the CPU; prints "platform": "cpu"
                            and is no measurement
    --control NAME:SHARE    the plain reference in the program's place
                            with one guarantee broken (harness/plain.py);
                            ``--control none`` runs it sound
    --fault NAME            the program with its timed path broken
                            underneath (harness/faults.py): how a limit's
                            upper reading is taken, never a measurement
    --set K=V               override a generator parameter: rate sweeps
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))   # the checkout: the program
sys.path.insert(0, _HERE)                    # harness/

from harness import deploy, faults, loadgen, plain, readers, reference, xplane  # noqa: E402
from harness.manifest import Manifest, resolve  # noqa: E402

TRACE_LABELS = ("raft-colocated-step", "raft-colocated-select")
HOST_ROWS = "engine.host_rows_stepped"


# the program's own counters that have to stay where they are, in every
# cell: a ratio of sums over the window or the whole run, a relation and a
# limit.  A workload's file adds its own under "health", and has to hold
# the rows stepped on the host engine to a limit there (against nothing,
# or against the operations that the window answered); it can take none
# of these out and change none.
def _h(key, over, rel, limit):
    return {"num": ["engine." + key], "over": over, "rel": rel,
            "limit": limit}


DEFAULT_HEALTH = {
    "device_rows_stepped": _h("device_rows_stepped", "window", ">=", 1),
    "retraces": _h("retraces", "run", "<=", 0),
    "pipeline_resets": _h("pipeline_resets", "run", "<=", 0),
    "step_worker_failures": _h("step_worker_failures", "run", "<=", 0),
    "divergence_halts": _h("divergence_halts", "run", "<=", 0),
    "save_failures": _h("save_failures", "run", "<=", 0),
}


def _say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _within(value, rel, limit) -> bool:
    if limit is None:
        return True
    if value is None:      # the counter was not there to be read
        return False
    return value <= limit if rel == "<=" else value >= limit


def health_checks(cell: dict) -> dict:
    own = cell.get("health", {})
    if set(own) & set(DEFAULT_HEALTH) or None in own.values():
        raise ValueError(f"{cell['name']}: a workload adds health checks; "
                         f"it takes out or changes none of "
                         f"{sorted(DEFAULT_HEALTH)}")
    if not any(HOST_ROWS in spec["num"] and spec["rel"] == "<="
               and spec["limit"] is not None for spec in own.values()):
        raise ValueError(f"{cell['name']}: its health has to hold "
                         f"{HOST_ROWS} to a limit")
    return {**DEFAULT_HEALTH, **own}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--shards", type=int, default=None,
                    help="with --dryrun or --control: cut the shard count")
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None, choices=sorted(faults.FAULTS))
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override a generator parameter (sweeps only; a "
                         "cell's own numbers live in its file)")
    args = ap.parse_args(argv)

    man = Manifest()
    cell = man.cell(args.workload)
    cfg = man.config(cell["config"])
    e2e, layers = man.end_to_end(cell["name"]), man.per_layer(cell["name"])
    health = health_checks(cell)
    for kv in args.set:
        k, _, v = kv.partition("=")
        cell["params"][k] = json.loads(v)
    if args.shards and not (args.dryrun or args.control):
        ap.error("--shards cuts the deployment: only with --dryrun/--control")
    bad_env = deploy.forbidden_env()
    if bad_env:
        print(f"benchmark: unset {bad_env}: the cell runs the program's "
              f"shipped settings", file=sys.stderr)
        return 2

    t_i = time.monotonic()
    import jax

    from dragonboat_tpu.ops import placement

    placement.configure_compile_cache(jax)  # before any backend starts
    devs = jax.devices()
    platform = devs[0].platform
    start_s = {"python_s": t_i - T_PROCESS, "jax_s": time.monotonic() - t_i}
    rehearsal = args.dryrun or args.control is not None
    if platform != "tpu" and not rehearsal:
        print(f"benchmark: no accelerator (platform {platform!r}); "
              f"--dryrun rehearses on the CPU", file=sys.stderr)
        return 1
    if len(devs) < cell["chips"] and not rehearsal:
        print(f"benchmark: {cell['name']} needs {cell['chips']} chips, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 1

    if args.control is not None:
        name, _, share = args.control.partition(":")
        system = plain.PlainCluster(
            cfg, args.shards, None if name == "none" else name,
            float(share or 0), args.seed)
    else:
        system = resolve(cfg["deployment"])(cfg, args.shards)

    trace_dir = None
    trace_s = min(cell.get("trace_s", 3.0), args.seconds / 2.0)
    tracing = threading.Event()
    state = {}

    def on_open() -> None:
        state["c0"] = system.counters()
        state["setup_s"] = time.monotonic() - T_PROCESS
        if args.trace:
            threading.Thread(target=start_trace, daemon=True,
                             name="bench-trace").start()

    def start_trace() -> None:
        # the last trace_s seconds of the window, not all of it: traces
        # are large and tracing slows the host
        time.sleep(max(0.0, args.seconds - trace_s))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing.set()

    def on_close() -> None:
        state["c1"] = system.counters()
        if args.trace:
            tracing.wait(30.0)
            jax.profiler.stop_trace()

    closed = {}
    beat = loadgen.Heartbeat()
    gcw = loadgen.GcWatch()
    gcw.install()
    try:
        system.build()
        if args.fault:
            faults.FAULTS[args.fault](system)
        gen = resolve(cell["generator"])(
            system, cell["params"], args.seed, args.seconds,
            system.n_shards, cfg)
        t_l = time.monotonic()
        gen.load()
        system.setup["load_s"] = time.monotonic() - t_l
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="dbtpu-trace-")
        t_g = time.monotonic()
        beat.start()
        gen.run(on_open, on_close)
        beat.stop()
        system.setup["generator_warmup_s"] = gen.t0 - t_g
        peak = system.memory_peak_bytes()
        t_c = time.monotonic()
        compared = reference.compare(gen, system, cell.get("compare", {}),
                                     args.seed)
        compared = {k: (v, "<=", lim) for k, (v, lim) in compared.items()}
        after = {"drain_s": t_c - gen.t1, "compare_s": time.monotonic() - t_c}
        state["c2"] = system.counters()   # the whole run, read-back included
    finally:
        beat.stop()
        gcw.remove()
        closed = system.close()

    win = loadgen.window_table(gen)
    win["table"].update({"loadgen." + k: v
                         for k, v in gcw.table(gen.t0, gen.t1).items()})
    delta = {k: state["c1"][k] - state["c0"].get(k, 0) for k in state["c1"]}
    table = {**delta, **win["table"]}
    for name, spec in health.items() if state["c2"] else ():
        src = table if spec["over"] == "window" else state["c2"]
        compared[name] = (readers.counter_ratio(spec, {"table": src}),
                          spec["rel"], spec["limit"])
    compared["leaked_threads"] = (len(closed["leaked_threads"]), "<=", 0)
    correct = all(_within(v, rel, lim) for v, rel, lim in compared.values())

    trace = None
    if args.trace:
        pb = xplane.find_xplane(trace_dir)
        trace = xplane.reduce_xplane(pb, TRACE_LABELS)
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {
        "table": table, "series": win["series"], "missing": win["missing"],
        "trace": trace, "config": cfg, "device_kind": devs[0].device_kind,
        "setup_s": state["setup_s"],
        "op_timeout_ms": gen.p["op_timeout_s"] * 1000.0,
    }
    metrics = readers.read_all(layers if args.trace else e2e, ctx)
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]

    # diagnostics: everything a reader of the log wants, on earlier lines
    both = readers.read_all(e2e + layers, ctx)
    _say(diag={
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "dryrun": args.dryrun, "control": args.control, "fault": args.fault,
        "shards": system.n_shards,
        "setup_split_s": {**start_s, **system.setup}, **system.diag, **after, **closed,
        "cache_entries_after": deploy.cache_entries(
            getattr(system, "cache_dir", None) or ""),
        "sweep_period_ms": (1e3 * (gen.t1 - gen.t0) / gen.sweeps
                            if gen.sweeps else None),
        "heartbeat_gap_max_ms": 1e3 * beat.max_gap_s,
        "stalls": [{"at_s": round(t - gen.t0, 3), "gap_s": round(g, 3),
                    "cpu_s": round(c, 3)} for t, g, c in beat.stalls],
        "gc_whole_run": gcw.by_generation(),
        "all_metrics": {k: v["value"] for k, v in both.items()},
        "loadgen": win["table"],
        "window_delta": {k: v for k, v in delta.items() if v},
        "programs": trace["programs"] if trace else None,
    })
    result = {
        "correct": correct,
        "attempted": win["table"]["loadgen.attempted"],
        "failed": win["table"]["loadgen.failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = {k: [v, rel, lim]
                          for k, (v, rel, lim) in compared.items()}
    sys.stdout.flush()
    for k, (v, rel, lim) in compared.items():
        mark = "" if _within(v, rel, lim) else "   <-- outside its limit"
        lim_s = "(counted, no limit)" if lim is None else f"limit {rel} {lim}"
        print(f"compared {k} = {v}  {lim_s}{mark}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
