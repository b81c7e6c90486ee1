"""The two per-layer metrics of PR 34 (what the client front costs now
that a gateway worker wakes on events: ``gw_cpu_us_per_op.sat`` = 1000 x
``gateway.t_worker_cpu_ms`` / ``gateway.committed``, the workers'
interpreter time an acknowledged operation; ``gw_timed_wake_pct.sat`` =
100 x ``gateway.wakes_timed`` / ``gateway.wakes``, the share of a
worker's wakes that the clock made and no event) are data alone: a
``counter_ratio`` file each and a ``per_layer`` entry.  Each has its
file, resolves, lists the three saturated cells, and reads a number in
the traced rehearsal of one of them on the CPU at 8 shards; where the
program has no such counters, as the parent has not, the metrics are
left out and nothing raises.
"""
import json
import os

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

NAMES = {"gw_cpu_us_per_op.sat": "us", "gw_timed_wake_pct.sat": "%"}
CELLS = ["ycsb-a-1k3.mixed-sat", "ycsb-a-10k5.mixed-sat",
         "ycsb-a-100k357.churn-sat"]
LAYER = "client front (gateway/gateway.py)"


def test_two_entries_each_with_a_file_a_reader_and_its_cells():
    man = Manifest()
    for cell in CELLS:
        by_name = {m["name"]: m for m in man.per_layer(cell)}
        for name, unit in NAMES.items():
            m = by_name[name]
            assert os.path.isfile(os.path.join(
                man.bench_dir, "layers", name + ".json"))
            assert m["reader"] == "harness.readers.counter_ratio"
            assert callable(resolve(m["reader"]))
            # `in`, not `==`: a later cell may be appended to the list
            assert cell in m["workloads"]
            assert m["source"] == "program_counter"
            assert m["layer"] == LAYER and m["moves"] == "ops_per_s"
            assert (m["unit"], m["better"]) == (unit, "lower")
    # the open-loop cell reports no `ops_per_s` for them to move
    assert not set(NAMES) & {
        m["name"] for m in man.per_layer("base-1k3.write-rate")}
    # after everything that was there (`index`, not a slice from the
    # end: a later entry goes behind these)
    names = [m["name"] for m in man.doc["per_layer"]]
    for name in NAMES:
        assert names.index(name) > names.index("routed_drop_pct.sat")
        assert names.count(name) == 1


def test_the_metrics_are_left_out_where_the_program_has_no_such_counters():
    man = Manifest()
    metrics = [m for m in man.per_layer(CELLS[2]) if m["name"] in NAMES]
    assert len(metrics) == 2
    parent = {"gateway.committed": 8000.0, "gateway.poll_checks": 2.4e5}
    assert readers.read_all(metrics, {"table": parent}) == {}
    change = dict(parent, **{"gateway.t_worker_cpu_ms": 200.0,
                             "gateway.wakes": 16000.0,
                             "gateway.wakes_timed": 40.0})
    assert readers.read_all(metrics, {"table": change}) == {
        "gw_cpu_us_per_op.sat": {"value": 25.0, "unit": "us"},
        "gw_timed_wake_pct.sat": {"value": 0.25, "unit": "%"}}
    # no timed wake at all is a reading, 0, and not a silence
    quiet = dict(change, **{"gateway.wakes_timed": 0.0})
    assert readers.read_all(metrics, {"table": quiet})[
        "gw_timed_wake_pct.sat"] == {"value": 0.0, "unit": "%"}
    # a window in which nothing committed and nobody woke reads nothing,
    # and does not divide by 0
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


def test_they_read_numbers_in_the_traced_rehearsal_of_a_cell(capsys):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7: `host_steps_per_op` over its limit); the counters and
    # the reader are what is rehearsed here
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                         "4", "--trace", "1", "--dryrun", "--shards", "8"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    cpu = last["metrics"].get("gw_cpu_us_per_op.sat")
    timed = last["metrics"].get("gw_timed_wake_pct.sat")
    assert cpu is not None and timed is not None, sorted(last["metrics"])
    assert cpu["value"] > 0.0
    assert 0.0 <= timed["value"] <= 100.0
    # a worker looks at a pair when it is notified, and once more only
    # after a DROPPED answer: not at every pass while it is pending
    assert last["metrics"]["gw_polls_per_op.sat"]["value"] < 4.0
