"""The plain reference and the comparison that decides ``correct``.

The reference is a replay: the run's operations, in the order the
generator saw them issued and answered, played into one plain register
per key.  It imports nothing of the program and takes nothing the program
made: values are made again from the seed and the write's number.

What a register allows a read to return.  A read R = [issue, answer]
returned the value of write W.  W must exist on that key, must have been
issued before R was answered, and must not have been overwritten for
certain: no acknowledged write W' lies wholly after W and wholly before R
(W.answer < W'.issue and W'.answer < R.issue).  A write that failed or
timed out may still take effect at any later time, so it is never counted
as overwritten.  The read-back after the window is such a read.

Numbers compared, each with the limit 0 (exact comparisons):
  unanswered        operations that never came back, drain included
  stale_reads       reads inside the window the register does not allow
  lin_mismatch      keys whose linearizable read-back, after the window,
                    is not allowed: every key written, unless the cell's
                    file cuts it to a sample drawn from the seed
  replica_mismatch  keys on which some replica's state machine did not
                    converge to the allowed value the others hold
and the program's own counters over the window (``health`` in run.py).
"""
from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .loadgen import (GOT, KEY, KIND, OK, PENDING, READ, SHARD,
                      SHED, STATUS, T_DONE, T_ISSUE, VID, WRITE)


# what a workload's ``compare`` may leave out; ``linearizable_sample``
# None reads back every key
COMPARE_DEFAULTS = {"linearizable_sample": None, "read_timeout_s": 30.0,
                    "read_threads": 64, "converge_s": 15.0}


class PlainRegisters:
    """One register per (shard, key), filled by replaying the op log."""

    def __init__(self, ops, values):
        self.values = values
        self.writes = {}   # (shard, key) -> {vid: (issue, answer, acked)}
        for op in ops:
            if op[KIND] != WRITE or op[STATUS] == SHED:
                continue  # a write shed at the door was never proposed
            acked = op[STATUS] == OK
            self.writes.setdefault((op[SHARD], op[KEY]), {})[op[VID]] = (
                op[T_ISSUE], op[T_DONE] if acked else math.inf, acked)

    def keys(self):
        return list(self.writes)

    def allows(self, shard, key, value, r_issue, r_answer) -> bool:
        ws = self.writes.get((shard, key), {})
        if value is None:  # nothing written: allowed until a write is acked
            return not any(a and ans < r_issue for _i, ans, a in ws.values())
        vid = self.values.decode(value)
        w = ws.get(vid)
        if w is None or self.values.encode(vid) != value:
            return False   # no write of this run made these bytes
        w_issue, w_answer, _acked = w
        if w_issue > r_answer:
            return False   # from the future
        return not any(a and w_answer < i2 and ans2 < r_issue
                       for i2, ans2, a in ws.values())


def compare(gen, system, params: dict, seed: int) -> dict:
    """Run after the window has closed and drained.  Returns
    ``{name: (number, limit)}``; ``correct`` is every number within its
    limit."""
    params = {**COMPARE_DEFAULTS, **params}
    regs = PlainRegisters(gen.ops, gen.values)
    out = {}
    out["unanswered"] = (sum(1 for op in gen.ops if op[STATUS] == PENDING), 0)

    reads = [op for op in gen.ops if op[KIND] == READ and op[STATUS] == OK]
    out["stale_reads"] = (sum(
        1 for op in reads
        if not regs.allows(op[SHARD], op[KEY], op[GOT], op[T_ISSUE],
                           op[T_DONE])), 0)

    keys = sorted(regs.keys())
    rng = np.random.default_rng(seed)
    n_lin = min(params["linearizable_sample"] or len(keys), len(keys))
    sample = [keys[i] for i in rng.choice(len(keys), n_lin, replace=False)]
    timeout = params["read_timeout_s"]
    t_lin = time.monotonic()

    def read_back(sk):
        t_i = time.monotonic()
        try:
            got = system.read(sk[0], sk[1], timeout)
        except Exception:  # noqa: BLE001 — an answer that never came
            return sk, None, False
        return sk, got, regs.allows(sk[0], sk[1], got, t_i, time.monotonic())

    # many at a time: a read that falls back to ReadIndex waits a launch
    with ThreadPoolExecutor(params["read_threads"]) as pool:
        answers = list(pool.map(read_back, sample))
    out["lin_read_back_s"] = (time.monotonic() - t_lin, None)
    lin = {sk: got for sk, got, ok in answers if ok}
    bad = sum(1 for _sk, _got, ok in answers if not ok)
    out["lin_mismatch"] = (bad, 0)

    # every replica's state machine, on every key written: all replicas
    # hold one value, the register allows it, and it is the value the
    # linearizable read-back saw
    wait_s = 0.0 if system.synchronous else params["converge_s"]
    deadline = time.monotonic() + wait_s
    pending = keys
    while True:
        t_r = time.monotonic()
        still = []
        for shard, key in pending:
            got = {system.replica_read(r, shard, key)
                   for r in system.replicas}
            if (shard, key) in lin:
                got.add(lin[(shard, key)])
            if len(got) != 1 or not regs.allows(shard, key, got.pop(),
                                                t_r, t_r):
                still.append((shard, key))
        pending = still
        if not pending or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    out["replica_mismatch"] = (len(pending), 0)
    out["keys_compared"] = (len(keys), None)
    out["reads_compared"] = (len(reads) + n_lin, None)
    return out
