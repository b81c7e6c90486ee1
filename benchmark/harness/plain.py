"""The plain reference put in the program's place: the control.

A replicated key-value store in a few lines: every write is applied to
every replica's dict before it is acknowledged, and a read returns the
first replica's value.  Run sound, the comparison passes it.  Run with
one of the configuration's guarantees broken (``--control NAME:SHARE``),
the comparison has to fail it:

  drop-acked    an acknowledged write is applied on no replica
                (breaks: an acknowledged write is durable on a quorum)
  stale-read    a linearizable read returns the value before the latest
                acknowledged write (breaks: a read returns the latest
                acknowledged value)
  replica-skip  the last replica skips the write
                (breaks: all replicas converge)

The broken share of operations is drawn from the seed.  Answers take
``latency_s`` to come, so that a closed loop does not spin.
"""
from __future__ import annotations

import random
import threading
import time

FAULTS = ("drop-acked", "stale-read", "replica-skip")


class _Future:
    __slots__ = ("_ready",)

    def __init__(self, ready: float):
        self._ready = ready

    def done(self) -> bool:
        return time.monotonic() >= self._ready

    def result(self, timeout=None):
        wait = self._ready - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        return 1


class _Handle:
    __slots__ = ("cluster", "shard")

    def __init__(self, cluster, shard):
        self.cluster, self.shard = cluster, shard

    def propose(self, cmd: bytes, timeout=None):
        return self.cluster._write(self.shard, cmd)


class PlainCluster:
    synchronous = True   # a write is on every replica before its answer

    def __init__(self, cfg: dict, shards: int | None = None,
                 fault: str | None = None, share: float = 0.0,
                 seed: int = 0, latency_s: float = 0.002):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown control {fault!r}; one of {FAULTS}")
        cl = cfg["cluster"]
        self.n_shards = shards or cl["shards"]
        self.replicas = list(range(1, cl["replicas"] + 1))
        self.fault, self.share = fault, share
        self.rng = random.Random(seed)
        self.latency_s = latency_s
        self.lock = threading.Lock()
        self.state = {r: {} for r in self.replicas}
        self.before = {}   # (shard, key) -> value before the latest write
        self.setup = {}
        self.diag = {"control": fault, "share": share}

    def build(self) -> None:
        pass

    def _hit(self, fault: str) -> bool:
        return self.fault == fault and self.rng.random() < self.share

    def _write(self, shard: int, cmd: bytes) -> _Future:
        k, v = cmd.decode().split("=", 1)
        with self.lock:
            if not self._hit("drop-acked"):
                skip = self._hit("replica-skip")
                self.before[(shard, k)] = self.state[1].get((shard, k))
                for r in self.replicas:
                    if not (skip and r == self.replicas[-1]):
                        self.state[r][(shard, k)] = v
        return _Future(time.monotonic() + self.latency_s)

    def handle(self, shard: int) -> _Handle:
        return _Handle(self, shard)

    def read(self, shard: int, key: str, timeout: float):
        time.sleep(self.latency_s)
        with self.lock:
            if self._hit("stale-read"):
                return self.before.get((shard, key))
            return self.state[1].get((shard, key))

    def replica_read(self, rid: int, shard: int, key: str):
        return self.state[rid].get((shard, key))

    def counters(self) -> dict:
        return {}

    def memory_peak_bytes(self) -> int:
        return 0

    def close(self) -> dict:
        return {"teardown_s": 0.0, "leaked_threads": []}
