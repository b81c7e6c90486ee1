"""Load generators: one general generator per way of offering load.

``FuturesOpen``    writes due on a schedule drawn from the seed, each on a
                   fresh handle and on a key of its own, so that every
                   acknowledged write can be read back.
``ThreadsClosed``  T client threads over a loaded record set, each
                   choosing read or update and a key as YCSB's scrambled
                   zipfian does.

A workload file names one by its dotted path (a later PR's generator is
a new module) and gives its parameters; what a file leaves out is
``PARAM_DEFAULTS``.  The program's futures carry no completion time and
no callback, so ``FuturesOpen`` keeps what is outstanding in ONE thread
that sweeps ``done()`` every ``sweep_ms`` and stamps completion at the
sweep.

An op is a list ``[kind, shard, key, vid, t_due, t_issue, t_done,
status, got]``; ``vid`` numbers a write and its value (``values.encode``).
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from . import traffic
from .manifest import resolve

# what a workload's ``params`` may leave out
PARAM_DEFAULTS = {"op_timeout_s": 60, "drain_s": 60, "warmup_s": 2.0,
                  "sweep_ms": 1, "late_open_s": 30.0}

WRITE, READ = 0, 1
PENDING, OK, FAILED, SHED = 0, 1, 2, 3
KIND, SHARD, KEY, VID, T_DUE, T_ISSUE, T_DONE, STATUS, GOT = range(9)
LOAD_VID_BASE = 0xFFFF << 32

now = time.monotonic


class Heartbeat:
    """A thread that only sleeps and wakes.  A long gap between two of its
    beats says that this process's threads were kept from running.  Each
    gap over ``stall_s`` is kept with the processor time the process used
    meanwhile: about the gap's length if one thread held the interpreter
    lock (a full garbage collection does, from start to end), about
    nought if the process or the machine was away."""

    def __init__(self, period_s: float = 0.05, stall_s: float = 1.0):
        self.period_s, self.stall_s = period_s, stall_s
        self.max_gap_s = 0.0
        self.stalls = []   # (start, gap seconds, processor seconds used)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-heartbeat")

    def _beat(self) -> None:
        last, cpu_last = now(), time.process_time()
        while not self._stop.wait(self.period_s):
            t, cpu = now(), time.process_time()
            if t - last > self.stall_s:
                self.stalls.append((last, t - last, cpu - cpu_last))
            self.max_gap_s = max(self.max_gap_s, t - last)
            last, cpu_last = t, cpu

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(5.0)


class GcWatch:
    """Times every garbage collection of the process (``gc.callbacks``):
    a collection holds the interpreter lock, so its length is a pause of
    every thread, the program's and the generator's alike."""

    def __init__(self):
        self.events = []   # (generation, start, seconds)
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = now()
        else:
            self.events.append((info["generation"], self._t,
                                now() - self._t))

    def install(self) -> None:
        gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def by_generation(self) -> dict:
        """Whole run: per generation [collections, total ms, longest ms]."""
        out = {}
        for g in (0, 1, 2):
            ms = [1e3 * e[2] for e in self.events if e[0] == g]
            out[f"gen{g}"] = [len(ms), round(sum(ms), 1),
                              round(max(ms, default=0.0), 1)]
        return out

    def table(self, t0: float, t1: float) -> dict:
        """Collections that began in [t0, t1): count, seconds, longest."""
        inside = [e for e in self.events if t0 <= e[1] < t1]
        return {
            "gc_collections": len(inside),
            "gc_full_collections": sum(1 for e in inside if e[0] == 2),
            "gc_pause_ms": 1e3 * sum(e[2] for e in inside),
            "gc_pause_max_ms": 1e3 * max((e[2] for e in inside), default=0.0),
        }


class _Base:
    def __init__(self, system, params: dict, seed: int, seconds: float,
                 n_shards: int, cfg: dict):
        self.system = system
        self.p = {**PARAM_DEFAULTS, **params}
        self.seed = seed
        self.seconds = float(seconds)
        self.n_shards = n_shards
        self.cfg = cfg
        self.values = resolve(params["value"]["kind"])(
            seed, **params["value"])
        self.ops = []          # every op of the run, load phase included
        self.t0 = self.t1 = None
        self.sweeps = 0
        self.sweep_busy_s = 0.0

    def load(self) -> None:
        """Load phase, part of set-up; most mixes have none."""

    # -- shared by the futures generators ------------------------------
    def _propose(self, handle, shard, key, vid, t_due, live, slot):
        op = [WRITE, shard, key, vid, t_due, 0.0, None, PENDING, None]
        cmd = f"{key}={self.values.encode(vid)}".encode()
        op[T_ISSUE] = now()
        try:
            live[slot] = (handle.propose(cmd, timeout=self.p["op_timeout_s"]),
                          op)
        except Exception as e:  # noqa: BLE001 — shed at the door, or closed
            op[T_DONE], op[STATUS], op[GOT] = now(), SHED, repr(e)
        self.ops.append(op)
        return op

    def _sweep(self, live: dict) -> list:
        """Stamp what completed since the last sweep; returns (slot, op)s."""
        t_s = now()
        done = [(slot, fo[1]) for slot, fo in live.items() if fo[0].done()]
        for slot, op in done:
            f, _op = live.pop(slot)
            op[T_DONE] = now()
            try:
                f.result(0)
                op[STATUS] = OK
            except Exception as e:  # noqa: BLE001 — failed or timed out
                op[STATUS], op[GOT] = FAILED, repr(e)
        self.sweeps += 1
        self.sweep_busy_s += now() - t_s
        return done

    def _drain(self, live: dict) -> None:
        deadline = now() + self.p["drain_s"]
        while live and now() < deadline:
            self._sweep(live)
            time.sleep(self.p["sweep_ms"] / 1000.0)


class FuturesOpen(_Base):
    """Every write has a key of its own (its shard's next), so that after
    the window each acknowledged write, and not only a key's last, is
    read back from the leader and from every replica."""

    def run(self, on_open, on_close) -> None:
        p = self.p
        sweep_s = p["sweep_ms"] / 1000.0
        total = p["warmup_s"] + self.seconds
        # a rehearsal on fewer shards offers each shard the same rate
        rate = p["rate_per_s"] * self.n_shards / self.cfg["cluster"]["shards"]
        offsets, shards = traffic.poisson_schedule(
            rate, total, self.n_shards, self.seed)
        # the window opens once the warm-up's arrivals are out and later
        # ones move with it: for an opening up to late_open_s late, the
        # schedule goes round again
        again = int(np.searchsorted(offsets, p["late_open_s"]))
        offsets = np.concatenate([offsets, offsets[-1] + offsets[:again]])
        shards = np.concatenate([shards, shards[:again]]).tolist()
        n = len(shards)
        used = [0] * (self.n_shards + 1)   # shard -> keys made so far
        live = {}
        t_begin = now()
        due = (t_begin + offsets).tolist()
        t_open = t_begin + p["warmup_s"]
        t1 = float("inf")
        shift = 0.0    # the window opened this late: later arrivals move
        i = 0
        while True:
            t = now()
            if t >= t1:
                break
            # until the window is open only the warm-up's arrivals go out
            while i < n and due[i] + shift <= t and (
                    self.t0 is not None or due[i] <= t_open):
                s = shards[i]
                k = traffic.key_name(used[s])
                used[s] += 1
                self._propose(self.system.handle(s), s, k, i, due[i] + shift,
                              live, i)
                i += 1
                t = now()
            if self.t0 is None and t >= t_open:
                # the warm-up's arrivals are out: the window opens now,
                # and lasts its whole length however late that is
                on_open()
                self.t0 = t = now()
                shift = t - t_open
                t1 = t + self.seconds
            self._sweep(live)
            nxt = due[i] + shift if i < n else t1
            spare = min(nxt, t1, t + sweep_s) - now()
            if spare > 0:
                time.sleep(spare)
        self.t1 = now()
        on_close()
        self._drain(live)


class ThreadsClosed(_Base):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        rec = self.cfg["records"]
        self.recordcount = rec["recordcount"]
        self.keys = traffic.ycsb_key_names(self.recordcount)
        self.key_shard = [1 + traffic.fnv64(k.encode()) % self.n_shards
                          for k in self.keys]

    def load(self) -> None:
        """YCSB's load phase: every record once, through the gateway."""
        live = {}
        todo = list(range(self.recordcount))
        cap = max(1, self.p["load_inflight"] * self.n_shards
                  // self.cfg["cluster"]["shards"])
        deadline = now() + self.p["op_timeout_s"]
        while todo or live:
            while todo and len(live) < cap:
                r = todo.pop()
                s = self.key_shard[r]
                op = self._propose(self.system.handle(s), s, self.keys[r],
                                   LOAD_VID_BASE | r, now(), live, r)
                if op[STATUS] == SHED:   # the door was full: once more
                    todo.insert(0, r)
                    break
            for r, op in self._sweep(live):
                if op[STATUS] != OK:
                    raise RuntimeError(f"load phase: record {r}: {op}")
            if now() > deadline:
                raise RuntimeError(f"load phase: {len(todo) + len(live)} of "
                                   f"{self.recordcount} records not loaded")
            time.sleep(0.002)

    def run(self, on_open, on_close) -> None:
        p = self.p
        n_thr, per = p["threads"], p["ops_per_thread"]
        total = n_thr * per
        rng = np.random.default_rng(self.seed)
        recs = traffic.scrambled_zipfian(
            self.recordcount, total, self.seed).reshape(n_thr, per).tolist()
        n_reads = int(round(total * p["read_share"]))
        is_read = np.arange(total) < n_reads
        rng.shuffle(is_read)
        is_read = is_read.reshape(n_thr, per).tolist()
        stop = threading.Event()
        go = threading.Event()
        logs = [[] for _ in range(n_thr)]
        timeout = p["op_timeout_s"]
        system, values = self.system, self.values
        keys, key_shard = self.keys, self.key_shard

        def client(tid: int) -> None:
            ops, mine, reads = logs[tid], recs[tid], is_read[tid]
            n = 0
            go.wait()
            while not stop.is_set():
                r = mine[n % per]
                k, s = keys[r], key_shard[r]
                if reads[n % per]:
                    t_i = now()
                    try:
                        got, st = system.read(s, k, timeout), OK
                    except Exception as e:  # noqa: BLE001
                        got, st = repr(e), FAILED
                    ops.append([READ, s, k, -1, t_i, t_i, now(), st, got])
                else:
                    vid = (tid << 32) | n
                    cmd = f"{k}={values.encode(vid)}".encode()
                    t_i = now()
                    try:
                        system.handle(s).propose(cmd, timeout=timeout).result(
                            timeout + 1.0)
                        got, st = None, OK
                    except Exception as e:  # noqa: BLE001
                        got, st = repr(e), FAILED
                    ops.append([WRITE, s, k, vid, t_i, t_i, now(), st, got])
                n += 1

        threads = [threading.Thread(target=client, args=(t,), daemon=True,
                                    name=f"bench-client-{t}")
                   for t in range(n_thr)]
        for t in threads:
            t.start()
        go.set()
        time.sleep(p["warmup_s"])
        on_open()
        self.t0 = now()
        time.sleep(max(0.0, self.t0 + self.seconds - now()))
        self.t1 = now()
        on_close()
        stop.set()
        for t in threads:
            t.join(timeout + 5.0)
        for t, log in zip(threads, logs):
            self.ops.extend(log)
            if t.is_alive():  # an operation that never came back
                self.ops.append([READ, 0, "", -1, self.t1, self.t1, None,
                                 PENDING, "client thread still waiting"])


def window_table(gen) -> dict:
    """``loadgen.*`` of the flat table, and the latency series, for ops of
    the window: due inside it (attempted) or answered inside it (acked)."""
    t0, t1 = gen.t0, gen.t1
    tab = {k: 0 for k in (
        "attempted", "acked", "failed", "shed", "unanswered",
        "writes_attempted", "reads_attempted", "writes_acked",
        "reads_acked")}
    series = {"late_ms": [], "write_from_due_ms": [], "read_ms": []}
    missing = {"write_from_due_ms": 0, "read_ms": 0}
    for op in gen.ops:
        kind = "reads" if op[KIND] == READ else "writes"
        done, st = op[T_DONE], op[STATUS]
        if st == OK and t0 <= done < t1:
            tab["acked"] += 1
            tab[kind + "_acked"] += 1
        if not t0 <= op[T_DUE] < t1:
            continue
        tab["attempted"] += 1
        tab[kind + "_attempted"] += 1
        series["late_ms"].append((op[T_ISSUE] - op[T_DUE]) * 1e3)
        name = "read_ms" if op[KIND] == READ else "write_from_due_ms"
        if st == OK:
            series[name].append((done - op[T_DUE]) * 1e3)
        else:
            missing[name] += 1
            tab["failed"] += 1
            tab["shed"] += st == SHED
            tab["unanswered"] += st == PENDING
    tab["window_s"] = t1 - t0
    # what was out at the middle and at the end: a queue that grows all
    # through the window says the rate offered is above what is sustained
    for name, at in (("outstanding_mid", (t0 + t1) / 2), ("outstanding_end",
                                                          t1)):
        tab[name] = sum(1 for op in gen.ops if op[T_ISSUE] <= at
                        and (op[T_DONE] is None or op[T_DONE] > at))
    tab["sweeps"] = gen.sweeps
    tab["sweep_busy_s"] = gen.sweep_busy_s
    return {"table": {"loadgen." + k: v for k, v in tab.items()},
            "series": series, "missing": missing}
