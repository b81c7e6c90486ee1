"""``harness.deploy.Deployment`` with on-disk state machines.

The configuration's ``state_machine`` names a factory of factories
(``root -> (shard_id, replica_id) -> IOnDiskStateMachine``, as
``dragonboat_tpu.bigstate.ondisk.text_kv_factory``).  Every replica's
state machine gets a directory of its own under this run's work
directory (``tempfile``: ``TMPDIR``), which ``close()`` removes, so no
run recovers from another's state.  Each keeps its own log open: the
open-file limit is checked, and raised if it can be, before the first
``start_replica``.  ``setup["sm_open_s"]`` is the time inside the state
machines' ``open()``, taken out of ``boot_s``.

``diag["update_latency_ms"]`` is the closed loop's update latency, YCSB's
``[UPDATE]`` percentiles: every proposal issued inside the window, from
the call to ``propose`` to the future's ``t_done``, one that failed or
never came back counted above all the others.  A diagnostic, judged by
nothing, until the benchmark has a metric for it (PERF.md section 7).
"""
from __future__ import annotations

import resource
import shutil
import tempfile
import time

from harness import traffic
from harness.deploy import Deployment
from harness.manifest import resolve

# descriptors besides the state machines' logs: the tan WALs, the native
# writer, JAX, the trace, the compile cache, sockets of the runtime
FD_HEADROOM = 512

# Deployment.build() takes the state machine from the configuration by
# dotted path; this module's replica_state_machine is that path, and the
# deployment being built (one a process) is who answers it
_building = None


def replica_state_machine(shard_id: int, replica_id: int):
    return _building.new_state_machine(shard_id, replica_id)


def raise_fd_limit(need: int) -> int:
    """Soft RLIMIT_NOFILE of at least ``need``, or an error that says
    so: never an EMFILE in the window."""
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != resource.RLIM_INFINITY and soft < need:
        if hard != resource.RLIM_INFINITY and hard < need:
            raise RuntimeError(
                f"this deployment keeps {need} files open (a log a "
                f"replica's state machine) and the hard open-file limit "
                f"is {hard}: raise it (ulimit -Hn) or cut the shards")
        resource.setrlimit(resource.RLIMIT_NOFILE, (need, hard))
        soft = need
    return soft


class _TimedHandle:
    """A gateway handle that keeps every proposal's issue time beside
    its future."""

    __slots__ = ("_handle", "_log")

    def __init__(self, handle, log: list):
        self._handle = handle
        self._log = log

    def propose(self, cmd: bytes, timeout=None):
        t_issue = time.monotonic()
        fut = self._handle.propose(cmd, timeout=timeout)
        self._log.append((t_issue, fut))
        return fut


def update_latency_ms(proposals, t0: float, t1: float) -> dict | None:
    """Percentiles over the proposals issued in ``[t0, t1)``."""
    lat, missing = [], 0
    for t_issue, fut in proposals:
        if not t0 <= t_issue < t1:
            continue
        try:
            fut.result(0)
            lat.append((fut.t_done - t_issue) * 1e3)
        except Exception:  # noqa: BLE001 - failed, timed out or still out
            missing += 1
    if not lat and not missing:
        return None
    return {"n": len(lat), "missing": missing, **{
        f"p{q}": traffic.percentile(lat, q, missing, missing=None)
        for q in (50, 95, 99)}}


class OnDiskDeployment(Deployment):
    def __init__(self, cfg: dict, shards: int | None = None):
        super().__init__({**cfg, "state_machine":
                          f"{__name__}.replica_state_machine"}, shards)
        self._make_factory = resolve(cfg["state_machine"])
        self.sm_dir = None
        self._factory = None
        self._sm_open_s = 0.0
        self._proposals = []
        # run.py reads the counters when the window opens and when it
        # closes (then once more after the read-back): the first two
        # readings bound the window
        self._counters_at = []

    def handle(self, shard: int):
        return _TimedHandle(super().handle(shard), self._proposals)

    def counters(self) -> dict:
        self._counters_at.append(time.monotonic())
        return super().counters()

    def new_state_machine(self, shard_id: int, replica_id: int):
        sm = self._factory(shard_id, replica_id)
        opened = sm.open

        def timed_open(stopc):
            t0 = time.monotonic()
            try:
                return opened(stopc)
            finally:
                self._sm_open_s += time.monotonic() - t0

        sm.open = timed_open
        return sm

    def build(self) -> None:
        global _building
        n_logs = self.n_shards * len(self.replicas)
        self.diag["fd_soft_limit"] = raise_fd_limit(n_logs + FD_HEADROOM)
        self.sm_dir = tempfile.mkdtemp(prefix="dbtpu-bench-sm-")
        self._factory = self._make_factory(self.sm_dir)
        _building = self
        try:
            super().build()
        finally:
            _building = None
        self.setup["sm_open_s"] = self._sm_open_s
        self.setup["boot_s"] -= self._sm_open_s

    def close(self) -> dict:
        if len(self._counters_at) >= 2:
            self.diag["update_latency_ms"] = update_latency_ms(
                self._proposals, *self._counters_at[:2])
        try:
            return super().close()
        finally:
            if self.sm_dir:
                shutil.rmtree(self.sm_dir, ignore_errors=True)
