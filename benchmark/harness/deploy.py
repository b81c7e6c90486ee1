"""Builds the deployment a configuration file describes, and takes it down.

The build, the election wait, the teardown and the health counters are
``chip_smoke.py``'s (PR 21, proved on the chip), copied here so that the
yardstick does not move when that script does.  The program is imported
only inside :class:`Deployment`; nothing else of the harness touches it.

Both :class:`Deployment` and ``plain.PlainCluster`` offer what the load
generators and the comparison use: ``handle(shard).propose(cmd, timeout)``
giving a future with ``done()`` / ``result(timeout)``, ``read``,
``replica_read``, ``replicas``, ``counters`` and ``close``.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time

from .manifest import resolve

ELECTION_DEADLINE_S = 300.0


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))


def fs_type(path: str) -> str:
    """Filesystem under ``path``: says whether the WAL's fsyncs met a disk."""
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mnt, fstype = line.split()[:3]
            if path.startswith(mnt) and len(mnt) > len(best):
                best, kind = mnt, fstype
    return kind


def forbidden_env() -> list:
    """The program's own switches: a run with one set is another program."""
    return sorted(k for k in os.environ if k.startswith("DRAGONBOAT_"))


class Deployment:
    """N shards x R replicas on R NodeHosts in this process, sharing one
    ColocatedEngineGroup on one device, a Gateway in front."""

    synchronous = False  # followers apply after the acknowledgement

    def __init__(self, cfg: dict, shards: int | None = None):
        self.cfg = cfg
        cl = cfg["cluster"]
        self.n_shards = shards or cl["shards"]
        self.replicas = list(range(1, cl["replicas"] + 1))
        self.shards = list(range(1, self.n_shards + 1))
        self.setup = {}
        self.nhs = {}
        self.gw = None
        self.group = None
        self.workdir = None
        self.cache_dir = None
        self.diag = {}

    # -- build ---------------------------------------------------------
    def build(self) -> None:
        import jax

        from dragonboat_tpu import (Config, EngineConfig, ExpertConfig,
                                    Gateway, GatewayConfig, NodeHost,
                                    NodeHostConfig)
        from dragonboat_tpu.analysis import jitcheck
        from dragonboat_tpu.native import load_walwriter
        from dragonboat_tpu.ops import placement
        from dragonboat_tpu.transport.inproc import reset_inproc_network

        cfg, cl = self.cfg, self.cfg["cluster"]
        self._jitcheck = jitcheck
        # honours JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
        self.cache_dir = placement.configure_compile_cache(jax)
        self.diag["cache_entries_before"] = cache_entries(self.cache_dir)
        nhc = cfg["nodehost"]
        if nhc["wal_writer"] == "native" and load_walwriter() is None:
            raise RuntimeError("native WAL writer did not build/load")
        logdb_factory = resolve(nhc["logdb_factory"])

        eng = dict(cfg["engine"])
        engine_group = resolve(eng.pop("factory"))
        if self.n_shards != cl["shards"]:  # --dryrun cut: smallest state
            rows = self.n_shards * cl["replicas"]
            eng["capacity"] = max(16, 1 << (rows - 1).bit_length())
        self.capacity = eng["capacity"]
        sm = resolve(cfg["state_machine"])

        jitcheck.enable(True)  # _warm() marks; any later compile is a retrace
        reset_inproc_network()
        self.group = engine_group(**eng)
        addrs = {r: f"bench-nh-{r}" for r in self.replicas}
        self.workdir = tempfile.mkdtemp(prefix="dbtpu-bench-")
        self.diag["wal_fs"] = fs_type(self.workdir)
        t0 = time.monotonic()
        for rid, addr in addrs.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=os.path.join(self.workdir, f"nh-{rid}"),
                rtt_millisecond=nhc["rtt_millisecond"],
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=nhc["exec_shards"],
                                        apply_shards=nhc["apply_shards"]),
                    step_engine_factory=self.group.factory,
                    logdb_factory=logdb_factory,
                ),
            ))
            if rid == self.replicas[0]:
                # the first NodeHost builds and warms the shared core:
                # every executable the run will use compiles or loads here
                self.setup["warm_s"] = time.monotonic() - t0
        self.diag["warm_programs"] = sum(jitcheck.Sentry().snapshot().values())
        self.diag["cache_entries_after_warm"] = cache_entries(self.cache_dir)
        self.gw = Gateway({addrs[r]: nh for r, nh in self.nhs.items()},
                          GatewayConfig(**cfg["gateway"]))

        t0 = time.monotonic()
        for nh in self.nhs.values():
            nh.pause_ticks()
        sc = cfg["shard"]
        for s in self.shards:
            for rid, nh in self.nhs.items():
                nh.start_replica(addrs, False, sm,
                                 Config(replica_id=rid, shard_id=s, **sc))
        for nh in self.nhs.values():
            nh.resume_ticks()
        self.setup["boot_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        first = self.nhs[self.replicas[0]]
        while True:
            covered = sum(1 for s in self.shards if first.get_leader_id(s)[1])
            if covered == len(self.shards):
                break
            if time.monotonic() - t0 > ELECTION_DEADLINE_S:
                raise RuntimeError(
                    f"leader coverage {covered}/{len(self.shards)} after "
                    f"{ELECTION_DEADLINE_S:.0f}s; "
                    f"engine={self.group.core.stats}")
            time.sleep(0.25)
        self.setup["election_s"] = time.monotonic() - t0

    # -- what the generators and the comparison drive ------------------
    def handle(self, shard: int):
        return self.gw.noop_handle(shard)

    def read(self, shard: int, key: str, timeout: float):
        return self.gw.read(shard, key, timeout=timeout)

    def replica_read(self, rid: int, shard: int, key: str):
        return self.nhs[rid].stale_read(shard, key)

    def counters(self) -> dict:
        """The flat table the ``counter_ratio`` reader divides: numeric
        entries of the engine's stats and of the gateway's."""
        out = {}
        for k, v in dict(self.group.core.stats).items():
            if isinstance(v, (int, float)):
                out["engine." + k] = v
        for k, v in self.gw.stats().items():
            if isinstance(v, (int, float)):
                out["gateway." + k] = v
        out["engine.step_worker_failures"] = sum(
            nh.engine.step_worker_failures for nh in self.nhs.values())
        out["engine.retraces"] = len(self._jitcheck.retraces())
        return out

    def memory_peak_bytes(self) -> int:
        import jax

        peak = 0
        for d in jax.devices():
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return peak

    # -- teardown ------------------------------------------------------
    def close(self) -> dict:
        t0 = time.monotonic()
        if self.gw is not None:
            self.gw.close()
        for nh in self.nhs.values():
            nh.close()
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)
        deadline = time.monotonic() + 10.0
        while True:
            leaked = [t.name for t in threading.enumerate()
                      if t.name.startswith("tpu-raft-") and t.is_alive()]
            if not leaked or time.monotonic() > deadline:
                break
            time.sleep(0.2)
        return {"teardown_s": time.monotonic() - t0, "leaked_threads": leaked}
