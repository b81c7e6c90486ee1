"""The per-layer metric of PR 31 (how often a launch renews a resident
leader's lease: ``lease_fresh_pct.sat`` = 100 x ``engine.lease_rows_fresh``
/ ``engine.lease_rows_armed``) is data alone: a ``counter_ratio`` file
and a ``per_layer`` entry.  It has its file, resolves, lists the two
``mixed-sat`` cells, and reads a number in the traced rehearsal of one of
them on the CPU at 8 shards; where the program has no such counters, as
the parent has not, the metric is left out and nothing raises.
"""
import json
import os

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

NAME = "lease_fresh_pct.sat"
CELLS = ["ycsb-a-1k3.mixed-sat", "ycsb-a-10k5.mixed-sat"]
LAYER = ("colocated engine, host side (ops/colocated.py, ops/engine.py, "
         "ops/hostplane.py)")


def test_one_entry_with_a_file_a_reader_and_its_cells():
    man = Manifest()
    for cell in CELLS:
        m = {m["name"]: m for m in man.per_layer(cell)}[NAME]
        assert os.path.isfile(os.path.join(
            man.bench_dir, "layers", NAME + ".json"))
        assert m["reader"] == "harness.readers.counter_ratio"
        assert callable(resolve(m["reader"]))
        # `in`, not `==`: a later cell may be appended to the list
        assert cell in m["workloads"]
        assert m["source"] == "program_counter"
        assert m["layer"] == LAYER and m["moves"] == "ops_per_s"
        assert (m["unit"], m["better"]) == ("%", "higher")
    # the cell that sends no read has no use for it
    assert NAME not in {
        m["name"] for m in man.per_layer("base-1k3.write-rate")}
    # after everything that was there (`index`, not a slice from the
    # end: a later entry goes behind this one)
    names = [m["name"] for m in man.doc["per_layer"]]
    assert names.index(NAME) > names.index("plan_ms.rate")
    assert names.count(NAME) == 1


def test_the_metric_is_left_out_where_the_program_has_no_such_counters():
    man = Manifest()
    metrics = [m for m in man.per_layer(CELLS[0]) if m["name"] == NAME]
    parent = {"engine.launches": 250.0, "engine.completion_rows_walked": 9e4}
    assert readers.read_all(metrics, {"table": parent}) == {}
    change = dict(parent, **{"engine.lease_rows_armed": 120000.0,
                             "engine.lease_rows_fresh": 105000.0})
    assert readers.read_all(metrics, {"table": change}) == {
        NAME: {"value": 87.5, "unit": "%"}}
    # a window in which no armed row was stepped reads nothing, and does
    # not divide by 0
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


def test_it_reads_a_share_in_the_traced_rehearsal_of_its_cell(capsys):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7: `host_steps_per_op` over its limit); the counters and
    # the reader are what is rehearsed here
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                         "4", "--trace", "1", "--dryrun", "--shards", "8"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    got = last["metrics"].get(NAME)
    assert got is not None, f"{NAME} is not in the traced line"
    assert isinstance(got["value"], float)
    # a share of the armed rows stepped: never over the whole
    assert 0.0 <= got["value"] <= 100.0
    assert last["metrics"]["lease_read_pct"]["value"] >= 0.0
