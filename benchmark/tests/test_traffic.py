import math

import numpy as np
import pytest

from harness import loadgen, traffic


def test_poisson_schedule_is_the_seeds_and_one_set_for_all_seeds():
    a = traffic.poisson_schedule(500.0, 4.0, 10, seed=2**31 + 7)
    b = traffic.poisson_schedule(500.0, 4.0, 10, seed=2**31 + 7)
    c = traffic.poisson_schedule(500.0, 4.0, 10, seed=5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    # same gaps and same shards, in another order
    assert np.allclose(np.sort(np.diff(a[0], prepend=0)),
                       np.sort(np.diff(c[0], prepend=0)))
    assert np.array_equal(np.sort(a[1]), np.sort(c[1]))
    assert len(a[0]) == 2000
    assert a[0][-1] == pytest.approx(4.0, rel=0.01)   # mean gap 1/rate
    assert set(np.bincount(a[1])[1:]) == {200}


def test_scrambled_zipfian_is_the_seeds_and_ycsbs_own():
    a = traffic.scrambled_zipfian(10_000, 200_000, seed=3_000_000_000)
    b = traffic.scrambled_zipfian(10_000, 200_000, seed=3_000_000_000)
    c = traffic.scrambled_zipfian(10_000, 200_000, seed=1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(np.sort(a), np.sort(c))
    counts = np.sort(np.bincount(a, minlength=10_000))[::-1] / len(a)
    # the hottest item of 1e10 draws 1/ZETAN, the second 1/(2^0.99 ZETAN)
    assert counts[0] == pytest.approx(1 / traffic.YCSB_ZETAN, abs=1e-3)
    assert 0.035 < counts[0] < 0.040
    assert counts[1] == pytest.approx(0.5 ** 0.99 / traffic.YCSB_ZETAN,
                                      abs=1e-3)
    assert a.min() >= 0 and a.max() < 10_000
    # the hottest record is where YCSB's hash puts item 0
    assert int(np.argmax(np.bincount(a))) == 6284781860667377211 % 10_000


def test_fnvhash64_and_key_names_are_ycsbs():
    # the first keys of every YCSB load with insertorder=hashed
    assert traffic.ycsb_key_names(2) == ["user6284781860667377211",
                                         "user8517097267634966620"]
    assert (traffic.fnvhash64([2**40 + 3]) >= 0).all()


def test_percentile_counts_missing_above_every_sample():
    vals = [float(i) for i in range(1, 96)]             # 95 answered
    assert traffic.percentile(vals, 50, n_missing=5) == 50.0
    assert traffic.percentile(vals, 95, n_missing=5) == 95.0
    assert traffic.percentile(vals, 96, n_missing=5) == math.inf
    assert traffic.percentile(vals, 99, 5, missing=60000.0) == 60000.0
    assert traffic.percentile([], 50) is None
    assert traffic.percentile([3.0, 1.0, 2.0], 100) == 3.0


def test_latency_is_taken_from_due_time_and_failures_count_as_missing():
    from harness import loadgen as lg

    class Gen:
        t0, t1, sweeps, sweep_busy_s = 10.0, 20.0, 1, 0.0
        ops = [
            # due 11, issued late at 11.5, answered 12: 1000 ms from due
            [lg.WRITE, 1, "a", 0, 11.0, 11.5, 12.0, lg.OK, None],
            [lg.WRITE, 1, "b", 1, 12.0, 12.0, 12.1, lg.OK, None],
            [lg.WRITE, 1, "c", 2, 13.0, 13.0, 13.2, lg.SHED, "busy"],
            [lg.WRITE, 1, "d", 3, 19.9, 19.9, 20.5, lg.OK, None],  # late ack
            [lg.WRITE, 1, "e", 4, 9.0, 9.0, 10.5, lg.OK, None],   # due before
            [lg.READ, 1, "a", -1, 14.0, 14.0, 14.004, lg.OK, "x"],
        ]

    w = lg.window_table(Gen)
    t = w["table"]
    assert t["loadgen.attempted"] == 5 and t["loadgen.failed"] == 1
    assert t["loadgen.shed"] == 1
    # acked INSIDE the window: a, b, e and the read; d was acked after it
    assert t["loadgen.acked"] == 4
    assert sorted(round(x) for x in w["series"]["write_from_due_ms"]) == [
        100, 600, 1000]
    assert w["missing"]["write_from_due_ms"] == 1
    assert [round(x) for x in w["series"]["late_ms"]][:1] == [500]
    assert [round(x) for x in w["series"]["read_ms"]] == [4]


@pytest.mark.parametrize("kind,kw", [("hex", {"digits": 14}),
                                     ("record", {"fieldcount": 10,
                                                 "fieldlength": 100})])
def test_values_are_the_seeds_and_each_write_has_its_own(kind, kw):
    make = {"hex": traffic.HexValues, "record": traffic.RecordValues}[kind]
    v, again, other = make(2**31 + 11, **kw), make(2**31 + 11, **kw), make(12, **kw)
    vids = [0, 1, 2, 77, (63 << 32) | 5, (0xFFFF << 32) | 9999]
    vals = [v.encode(i) for i in vids]
    assert vals == [again.encode(i) for i in vids]
    assert vals != [other.encode(i) for i in vids]
    assert len(set(vals)) == len(vals)
    assert [v.decode(x) for x in vals] == vids
    size = 14 if kind == "hex" else 1000
    assert all(len(x) == size for x in vals)
    if kind == "record":
        assert v.decode(vals[0][:-1] + "#") is None    # one byte altered


def test_keys_are_short_and_distinct():
    names = [traffic.key_name(i) for i in range(2000)]
    assert len(set(names)) == 2000
    assert names[:3] == ["a", "b", "c"] and len(names[35]) == 1
    assert len(names[36]) == 2
    assert traffic.fnv64(b"user1") != traffic.fnv64(b"user2")


def test_gc_watch_counts_the_pauses_that_began_in_the_window():
    w = loadgen.GcWatch()
    w.events = [(0, 0.5, 0.001), (2, 1.0, 0.25), (1, 1.5, 0.004),
                (2, 2.5, 0.3)]
    tab = w.table(1.0, 2.0)
    assert tab["gc_collections"] == 2 and tab["gc_full_collections"] == 1
    assert tab["gc_pause_ms"] == pytest.approx(254.0)
    assert tab["gc_pause_max_ms"] == pytest.approx(250.0)
    assert w.table(5.0, 6.0)["gc_pause_max_ms"] == 0.0
    w.install()
    try:
        import gc
        gc.collect()
    finally:
        w.remove()
    assert w.events[-1][0] == 2 and w.events[-1][2] > 0.0


def test_heartbeat_keeps_a_stall_with_the_processor_time_it_used():
    import time

    beat = loadgen.Heartbeat(period_s=0.01, stall_s=0.2)
    beat.start()
    time.sleep(0.1)
    t_end = time.monotonic() + 0.4
    while time.monotonic() < t_end:   # hold the interpreter lock, mostly
        sum(range(20000))
    beat.stop()
    assert beat.max_gap_s >= 0.01
    for _at, gap, cpu in beat.stalls:  # a stall, if the lock was kept
        assert gap > 0.2 and cpu >= 0.0


def test_a_window_that_opens_late_still_gets_all_its_arrivals():
    """A stall in the warm-up delays the opening; the arrivals move with
    it and the schedule goes round again, so the window is offered its
    whole load."""
    import time

    from harness import plain

    class Stalls(plain.PlainCluster):
        stalled = False

        def _write(self, shard, cmd):
            if not self.stalled:       # the first write: every thread stops
                self.stalled = True
                time.sleep(1.0)
            return super()._write(shard, cmd)

    cfg = {"cluster": {"shards": 4, "replicas": 3}}
    params = {"rate_per_s": 400, "warmup_s": 0.5,
              "value": {"kind": "harness.traffic.HexValues", "digits": 14}}
    gen = loadgen.FuturesOpen(Stalls(cfg), params, 2**31 + 5, 2.0, 4, cfg)
    opened = []
    gen.run(lambda: opened.append(time.monotonic()), lambda: None)
    tab = loadgen.window_table(gen)["table"]
    assert gen.t1 - gen.t0 == pytest.approx(2.0, abs=0.05)
    assert tab["loadgen.attempted"] == pytest.approx(800, rel=0.05)
    assert tab["loadgen.failed"] == 0
    # every write has a key of its own
    keys = [(op[loadgen.SHARD], op[loadgen.KEY]) for op in gen.ops]
    assert len(set(keys)) == len(keys)
