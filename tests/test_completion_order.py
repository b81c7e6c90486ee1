"""No update outlives the completion that emitted it (ROADMAP Reach A2).

A colocated generation's early pass (``_lane_commit_pass``) persists its
rows and hands their committed entries to apply at once.  When one step
call completed TWO generations before persisting (both readbacks landed
between two calls: rare at 4,096 rows x 3 on the chip, usual on the CPU
and at 8,192 x 5), a classic update of the first generation was saved
and processed AFTER the second one's early pass for the same node: an
older hard state written over a newer one, and ``RuntimeError: invalid
processed 13 (processed=14 committed=14)`` out of ``log.commit_update``,
which PERF.md section 7 had carried since PR 21 as the step worker's
failure "under concentrated 1 KB load".

The load is the benchmark's load phase at 8 groups x 5: 64 proposals of
1 KB records in flight, so every group streams appends whose commit the
followers learn one generation later.  The order is checked directly
(every completion begins with nothing unsaved), with the pipeline made
to hold two generations every other call, and by its symptom.
"""
import shutil
import tempfile
import time

import pytest

from dragonboat_tpu.ops import colocated

import test_ondisk_served as ods

N_WRITES = 1500
IN_FLIGHT = 64


class _Streamed(ods.Served):
    def _run(self) -> None:
        live, todo = {}, list(range(N_WRITES))
        deadline = time.monotonic() + 120.0
        while todo or live:
            while todo and len(live) < IN_FLIGHT:
                i = todo.pop()
                r = i % ods.RECORDS
                cmd = f"{self.keys[r]}={self.values.encode(i)}".encode()
                live[i] = self.gw.noop_handle(self.key_shard[r]).propose(
                    cmd, timeout=ods.OP_TIMEOUT_S)
            for i in [i for i, f in live.items() if f.done()]:
                live.pop(i).result(0)
            assert time.monotonic() < deadline, (len(todo), len(live))
            time.sleep(0.002)

    def _settle(self) -> None:
        sms = [self._node(rid, s).sm.managed.sm
               for s in range(1, ods.N_SHARDS + 1) for rid in ods.REPLICAS]
        deadline = time.monotonic() + 15.0
        while True:     # followers apply after the acknowledgement
            self.applied_writes = sum(sm.wal_counts()[0] for sm in sms)
            if (self.applied_writes >= 5 * N_WRITES
                    or time.monotonic() > deadline):
                break
            time.sleep(0.05)
        self.stats = dict(self.group.core.stats)
        self.step_worker_failures = sum(
            nh.engine.step_worker_failures for nh in self.nhs.values())


@pytest.fixture(scope="module")
def streamed():
    """One run, with every other step call's ripe pass held back by a
    sync floor so that the next call finds two generations landed, and
    the order of completions and persists recorded."""
    real_step = colocated.ColocatedVectorEngine._step_colocated
    real_complete = colocated.ColocatedVectorEngine._complete_oldest
    real_persist = colocated.ColocatedVectorEngine._persist_and_process
    calls, unsaved, seen = [0], set(), {"began_with_unsaved": 0, "double": 0}

    def step(self, nodes, worker_id):
        calls[0] += 1
        self._sync_floor_s = 0.0 if calls[0] % 2 else 0.05
        seen["in_call"] = 0
        return real_step(self, nodes, worker_id)

    def complete(self):
        seen["in_call"] = seen.get("in_call", 0) + 1
        seen["double"] += seen["in_call"] == 2
        seen["began_with_unsaved"] += bool(unsaved)
        updates = real_complete(self)
        unsaved.update(id(u) for _node, u in updates)
        return updates

    def persist(self, updates, worker_id):
        unsaved.difference_update(id(u) for _node, u in updates)
        return real_persist(self, updates, worker_id)

    mp = pytest.MonkeyPatch()
    mp.setattr(colocated.ColocatedVectorEngine, "_step_colocated", step)
    mp.setattr(colocated.ColocatedVectorEngine, "_complete_oldest", complete)
    mp.setattr(colocated.ColocatedVectorEngine, "_persist_and_process",
               persist)
    root = tempfile.mkdtemp(prefix="ods-order-")
    try:
        run = _Streamed("colocated", root)
        run.close()
        yield run, seen
    finally:
        mp.undo()
        shutil.rmtree(root, ignore_errors=True)


def test_two_generations_did_complete_in_one_call(streamed):
    _run, seen = streamed
    assert seen["double"] > 10      # or the next test shows nothing


def test_every_completion_begins_with_nothing_unsaved(streamed):
    _run, seen = streamed
    assert seen["began_with_unsaved"] == 0


def test_no_step_worker_failed_and_every_write_was_applied_five_times(
        streamed):
    run, _seen = streamed
    assert run.step_worker_failures == 0
    assert run.stats["pipeline_resets"] == 0
    assert run.applied_writes == 5 * N_WRITES
