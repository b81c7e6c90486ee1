#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the accelerator.

    python chip_smoke.py                       # one TPU chip
    python chip_smoke.py --chips 4             # one four-chip host
    python chip_smoke.py --allow-cpu --shards 8   # CPU dry run (tests)

Builds BASELINE config 2 at its stated size — 1,000 raft groups x 3
replicas, 3,000 live rows in a 4,096-row device state — through the
entry points a service uses (three ``NodeHost``s sharing one
``ColocatedEngineGroup``, the tan WAL on the native writer, a
``Gateway`` in front), waits for every group to elect, commits one
16-byte write per group through the gateway, reads the values back
linearizably and from every replica, closes everything, and prints two
JSON lines: the run's report, then — last, and with these keys only —
the verdict the driver reads,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only if every check passed; an exception in any phase ends
the run with neither line.

The numbers in the report are one unrepeated run's observations for the
next issue to plan from.  None of them is a benchmark metric.

This script builds the three-replica geometry only.  The five-replica
smoke (BASELINE config 3 cut to 1,000 groups x 5 on an 8,192-row P=5 state,
on-disk state machines) is the benchmark's own cell, which builds the
deployment from its configuration's file and holds it to the same checks:

    python benchmark/run.py --workload ycsb-a-10k5.mixed-sat --seed 1 --seconds 20 --trace 0

One process, no children: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from importlib import metadata

import jax
import numpy as np

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    Gateway,
    GatewayConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.analysis import jitcheck
from dragonboat_tpu.native import load_walwriter
from dragonboat_tpu.ops import placement
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.storage.tan import tan_logdb_factory
from dragonboat_tpu.transport.inproc import reset_inproc_network
from examples.kv_gateway import KV  # cmd b"key=value"; lookup key -> value

REPLICAS = 3
READ_SAMPLE = 64
KEY = "k"
ELECTION_DEADLINE_S = 300.0
WRITE_TIMEOUT_S = 60.0
READ_TIMEOUT_S = 30.0
REPLICA_CONVERGE_S = 15.0


def _cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))


def _fs_type(path: str) -> str:
    """Filesystem under ``path`` (longest mount-point prefix): says
    whether the WAL's fsyncs met a disk or memory."""
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mnt, fstype = line.split()[:3]
            if path.startswith(mnt) and len(mnt) > len(best):
                best, kind = mnt, fstype
    return kind


def _pkg_version(name: str) -> str:
    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return "not installed"


def _readback_probe(dev, n: int = 200) -> dict:
    """Request->ready latency of one small device->host copy behind one
    trivial program: host clock from dispatch to the bytes landing."""
    f = jax.jit(lambda a: a + 1)
    x = jax.device_put(np.zeros((4096,), np.int32), dev)
    np.asarray(f(x))  # compile
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        y = f(x)
        y.copy_to_host_async()
        np.asarray(y)
        lat.append((time.perf_counter() - t0) * 1000.0)
    lat.sort()
    return {"n": n, "p50_ms": lat[n // 2], "p99_ms": lat[int(n * 0.99)],
            "min_ms": lat[0], "bytes": 4096 * 4}


def _wait_no_engine_threads(deadline_s: float = 10.0) -> list:
    deadline = time.monotonic() + deadline_s
    while True:
        leaked = [
            t.name for t in threading.enumerate()
            if t.name.startswith("tpu-raft-") and t.is_alive()
        ]
        if not leaked or time.monotonic() > deadline:
            return leaked
        time.sleep(0.2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shards", type=int, default=1000,
                    help="raft groups (x3 replicas); default is "
                         "BASELINE config 2's 1000")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 = one device; 4 = the engine's mesh= path "
                         "over four")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="dry run without an accelerator; the output "
                         'says "dryrun": true')
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    # before any backend starts; JAX_PLATFORMS is left as found
    cache_dir = placement.configure_compile_cache(jax)
    cache_before = _cache_entries(cache_dir)

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print(
            f"chip_smoke: no accelerator (jax.devices()[0].platform == "
            f"{platform!r}); --allow-cpu makes a labelled dry run",
            file=sys.stderr,
        )
        return 1
    dryrun = platform != "tpu"

    # built from walwriter.cpp on this machine; tan would otherwise take
    # its pure-Python writer without a word
    if load_walwriter() is None:
        print("chip_smoke: native WAL writer did not build/load",
              file=sys.stderr)
        return 1

    mesh = placement.groups_mesh(args.chips, jax)  # None for one chip
    probe = _readback_probe(placement.default_device(jax))

    shards = list(range(1, args.shards + 1))
    capacity = 1 << (args.shards * REPLICAS - 1).bit_length()
    rng = random.Random(args.seed)
    # 16-byte commands: b"k=" + 14 hex digits, one per shard, from --seed
    values = {s: f"{rng.getrandbits(56):014x}" for s in shards}

    jitcheck.enable(True)  # _warm() marks; any later compile is a retrace
    reset_inproc_network()
    # BASELINE config 2's geometry; pipeline depth and fused rounds at
    # their shipped defaults, the link-latency simulator off
    group = ColocatedEngineGroup(
        capacity=capacity, P=3, W=16, M=8, E=4, O=32, budget=4,
        sync_floor_ms=0.0, mesh=mesh,
    )
    addrs = {r: f"smoke-nh-{r}" for r in range(1, REPLICAS + 1)}
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    nhs = {}
    gw = None
    setup = {}
    try:
        t0 = time.monotonic()
        for rid, addr in addrs.items():
            nhs[rid] = NodeHost(
                NodeHostConfig(
                    nodehost_dir=os.path.join(workdir, f"nh-{rid}"),
                    rtt_millisecond=20,
                    raft_address=addr,
                    expert=ExpertConfig(
                        engine=EngineConfig(exec_shards=1, apply_shards=4),
                        step_engine_factory=group.factory,
                        logdb_factory=tan_logdb_factory,
                    ),
                )
            )
            if rid == 1:
                # the first NodeHost builds and warms the shared core:
                # every executable the run will use compiles here
                setup["warmup_s"] = time.monotonic() - t0
        warm_programs = sum(jitcheck.Sentry().snapshot().values())
        cache_after_warm = _cache_entries(cache_dir)
        gw = Gateway(
            {addrs[rid]: nh for rid, nh in nhs.items()},
            GatewayConfig(workers=4),
        )

        t0 = time.monotonic()
        for nh in nhs.values():
            nh.pause_ticks()
        for s in shards:
            for rid, nh in nhs.items():
                nh.start_replica(
                    addrs, False, KV,
                    Config(replica_id=rid, shard_id=s, election_rtt=20,
                           heartbeat_rtt=2, pre_vote=True,
                           check_quorum=True, snapshot_entries=0),
                )
        for nh in nhs.values():
            nh.resume_ticks()
        setup["boot_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        while True:
            covered = sum(1 for s in shards if nhs[1].get_leader_id(s)[1])
            if covered == len(shards):
                break
            if time.monotonic() - t0 > ELECTION_DEADLINE_S:
                raise RuntimeError(
                    f"leader coverage {covered}/{len(shards)} after "
                    f"{ELECTION_DEADLINE_S:.0f}s; engine={group.core.stats}"
                )
            time.sleep(0.25)
        setup["election_s"] = time.monotonic() - t0

        # ---- write window: one acknowledged write per group ---------
        stats0 = dict(group.core.stats)
        t0 = time.monotonic()
        futures = {
            s: gw.noop_handle(s).propose(
                f"{KEY}={values[s]}".encode(), timeout=WRITE_TIMEOUT_S
            )
            for s in shards
        }
        # .result raises on a shed, failed or timed-out operation
        acked = sum(
            1 for s in shards
            if futures[s].result(WRITE_TIMEOUT_S + 1.0) is not None
        )
        write_s = time.monotonic() - t0
        stats1 = dict(group.core.stats)

        # ---- reads: the acknowledged value, from the leader's lease /
        # ReadIndex path and then from each replica's state machine ----
        sample = rng.sample(shards, min(READ_SAMPLE, len(shards)))
        lin_ok = replica_ok = 0
        for s in sample:
            got = gw.read(s, KEY, timeout=READ_TIMEOUT_S)
            if got != values[s]:
                raise RuntimeError(
                    f"linearizable read shard {s}: {got!r} != {values[s]!r}"
                )
            lin_ok += 1
            for rid, nh in nhs.items():
                deadline = time.monotonic() + REPLICA_CONVERGE_S
                while nh.stale_read(s, KEY) != values[s]:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"replica {rid} of shard {s} never applied "
                            f"the acknowledged write"
                        )
                    time.sleep(0.01)
                replica_ok += 1

        mesh_checks = {}
        if mesh is not None:
            # the two checks of __graft_entry__'s engine-on-mesh dry run
            span = len(group.core._state.rows.sharding.device_set)
            blocks = {nhs[1].engine.device_coordinate(s) for s in shards}
            mesh_checks = {
                "state_spans_all_devices": span == args.chips,
                "live_rows_on_every_block":
                    blocks == set(range(args.chips)),
            }
    finally:
        if gw is not None:
            gw.close()
        for nh in nhs.values():
            nh.close()
        shutil.rmtree(workdir, ignore_errors=True)

    leaked = _wait_no_engine_threads()
    retraces = jitcheck.retraces()
    stats = dict(group.core.stats)
    worker_failures = sum(nh.engine.step_worker_failures
                          for nh in nhs.values())
    host_rows_in_window = (
        stats1["host_rows_stepped"] - stats0["host_rows_stepped"]
    )
    checks = {
        # coverage and both kinds of read raised above if they failed
        "writes_acked": acked == len(shards),
        "no_retrace_after_warmup": retraces == [],
        "device_rows_stepped": stats["device_rows_stepped"] > 0,
        "routed_delivered": stats["routed_delivered"] > 0,
        "no_escalations": stats["escalations"] == 0,
        "no_divergence_halts": stats["divergence_halts"] == 0,
        "no_save_failures": stats["save_failures"] == 0,
        "no_pipeline_resets": stats["pipeline_resets"] == 0,
        "no_step_worker_failures": worker_failures == 0,
        "no_host_rows_in_write_window": host_rows_in_window == 0,
        "no_engine_thread_leak": leaked == [],
        **mesh_checks,
    }
    # the verdict: exactly these keys, the device as JAX reports it
    verdict = {
        "ok": all(checks.values()),
        "device": {
            "platform": platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }
    launches = stats["launches"]
    report = {
        **verdict,
        "dryrun": dryrun,
        "chips": args.chips,
        "versions": {
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "jaxlib": _pkg_version("jaxlib"),
            "libtpu": _pkg_version("libtpu"),
        },
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": cache_before,
            "entries_after_warmup": cache_after_warm,
            "entries_after": _cache_entries(cache_dir),
            "warm_programs": warm_programs,
        },
        "wal_writer": "native",
        "wal_fs": _fs_type(workdir),
        "shards": len(shards),
        "replicas": REPLICAS,
        "capacity": capacity,
        "seed": args.seed,
        "setup_s": setup,
        "leader_coverage": f"{covered}/{len(shards)}",
        "writes": {"acked": acked, "failed": len(shards) - acked,
                   "bytes_each": 16, "window_s": write_s},
        "reads": {"linearizable_ok": f"{lin_ok}/{len(sample)}",
                  "replica_ok": f"{replica_ok}/{REPLICAS * len(sample)}"},
        "jitcheck_retraces": [list(r) for r in retraces],
        "step_worker_failures": worker_failures,
        "host_rows_stepped_in_write_window": host_rows_in_window,
        "leaked_threads": leaked,
        "engine": stats,
        "engine_write_window": {
            k: stats1[k] - stats0.get(k, 0)
            for k in sorted(stats1)
            if isinstance(stats1[k], (int, float))
        },
        "blob_wait_ms_per_launch": (
            stats.get("t_dev_blob_ms", 0) / launches if launches else None
        ),
        "readback_probe": probe,
        "checks": checks,
        "total_s": time.monotonic() - t_start,
    }
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
