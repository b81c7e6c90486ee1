"""The four per-layer metrics of PR 29 (the rows a launch's completion
walks in Python, and what its plan phase costs) are data alone: a
``counter_ratio`` file each and a ``per_layer`` entry.  Each has its
file, resolves, lists its cells, and reads a number in the traced
rehearsal of a cell on the CPU at 8 shards; where the program has no
``completion_rows_walked`` counter, as the parent has not, that metric is
left out and nothing raises.
"""
import json
import os

import pytest

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

NEW = {
    "base-1k3.write-rate": ["completion_rows_per_launch.rate", "plan_ms.rate"],
    "ycsb-a-1k3.mixed-sat": ["completion_rows_per_launch.sat", "plan_ms.sat"],
    "ycsb-a-10k5.mixed-sat": ["completion_rows_per_launch.sat", "plan_ms.sat"],
}
LAYER = ("colocated engine, host side (ops/colocated.py, ops/engine.py, "
         "ops/hostplane.py)")
MOVES = {"base-1k3.write-rate": "write_p95_ms",
         "ycsb-a-1k3.mixed-sat": "ops_per_s",
         "ycsb-a-10k5.mixed-sat": "ops_per_s"}


def test_four_entries_each_with_a_file_a_reader_and_its_cells():
    man = Manifest()
    for cell, names in NEW.items():
        by_name = {m["name"]: m for m in man.per_layer(cell)}
        for name in names:
            m = by_name[name]
            assert os.path.isfile(os.path.join(
                man.bench_dir, "layers", name + ".json"))
            assert m["reader"] == "harness.readers.counter_ratio"
            assert callable(resolve(m["reader"]))
            # `in`, not `==`: a later cell may be appended to the list
            assert cell in m["workloads"]
            assert m["source"] == "program_counter"
            assert m["layer"] == LAYER and m["moves"] == MOVES[cell]
            assert (m["unit"], m["better"]) == (
                ("ms", "lower") if name.startswith("plan_ms")
                else ("rows", "lower"))
    names = [m["name"] for m in Manifest().doc["per_layer"]]
    assert names[-4:] == [
        "completion_rows_per_launch.sat", "completion_rows_per_launch.rate",
        "plan_ms.sat", "plan_ms.rate"], "new entries go at the end"


def test_the_rows_are_left_out_where_the_program_has_no_such_counter():
    man = Manifest()
    metrics = {m["name"]: m for cell in NEW for m in man.per_layer(cell)
               if m["name"] in NEW[cell]}.values()
    parent = {"engine.t_plan_ms": 2750.0, "engine.launches": 125.0,
              "engine.device_rows_stepped": 257500.0}
    got = readers.read_all(metrics, {"table": parent})
    assert sorted(got) == ["plan_ms.rate", "plan_ms.sat"]
    assert got["plan_ms.sat"] == {"value": 22.0, "unit": "ms"}
    change = dict(parent, **{"engine.completion_rows_walked": 40000.0})
    got = readers.read_all(metrics, {"table": change})
    assert sorted(got) == sorted({n for names in NEW.values() for n in names})
    assert got["completion_rows_per_launch.rate"] == {
        "value": 320.0, "unit": "rows"}
    # a window without a launch reads nothing, and does not divide by 0
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


@pytest.mark.parametrize("cell", ["base-1k3.write-rate",
                                  "ycsb-a-1k3.mixed-sat"])
def test_each_reads_a_number_in_the_traced_rehearsal_of_its_cell(
        capsys, cell):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7: `host_steps_per_op` over its limit); the counters and
    # the readers are what is rehearsed here
    rc = bench_run.main(["--workload", cell, "--seed", "1", "--seconds", "4",
                         "--trace", "1", "--dryrun", "--shards", "8"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    for name in NEW[cell]:
        got = last["metrics"].get(name)
        assert got is not None, f"{name} is not in the traced line"
        assert isinstance(got["value"], float) and got["value"] >= 0.0
    suffix = name.rpartition(".")[2]
    walked = last["metrics"]["completion_rows_per_launch." + suffix]
    # 24 rows resident: no launch walks more than a few times that
    assert 0.0 < walked["value"] < 24 * 3 * 2
