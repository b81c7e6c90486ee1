"""The four per-layer metrics of PR 27 (what a launch's encode phase
costs, and the share of its rows that took the tick lane) are data
alone: a ``counter_ratio`` file each and a ``per_layer`` entry.  Each has
its file, resolves, and reads a number in the traced rehearsal of its
cell on the CPU at 8 shards; where the program has no ``tick_lane_rows``
counter, as the parent has not, the share is left out and nothing raises.
"""
import json
import os

import pytest

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

NEW = {
    "base-1k3.write-rate": ["encode_ms.rate", "tick_lane_pct.rate"],
    "ycsb-a-1k3.mixed-sat": ["encode_ms.sat", "tick_lane_pct.sat"],
}
LAYER = ("colocated engine, host side (ops/colocated.py, ops/engine.py, "
         "ops/hostplane.py)")
MOVES = {"base-1k3.write-rate": "write_p95_ms",
         "ycsb-a-1k3.mixed-sat": "ops_per_s"}


def test_four_entries_each_with_a_file_a_reader_and_one_cell():
    man = Manifest()
    for cell, names in NEW.items():
        by_name = {m["name"]: m for m in man.per_layer(cell)}
        for name in names:
            m = by_name[name]
            assert os.path.isfile(os.path.join(
                man.bench_dir, "layers", name + ".json"))
            assert m["reader"] == "harness.readers.counter_ratio"
            assert callable(resolve(m["reader"]))
            assert m["workloads"] == [cell]
            assert m["source"] == "program_counter"
            assert m["layer"] == LAYER and m["moves"] == MOVES[cell]
            assert (m["unit"], m["better"]) == (
                ("ms", "lower") if name.startswith("encode_ms")
                else ("%", "higher"))


def test_the_share_is_left_out_where_the_program_has_no_such_counter():
    man = Manifest()
    metrics = [m for cell in NEW for m in man.per_layer(cell)
               if m["name"] in NEW[cell]]
    parent = {"engine.t_encode_ms": 5000.0, "engine.launches": 125.0,
              "engine.device_rows_stepped": 257500.0}
    got = readers.read_all(metrics, {"table": parent})
    assert sorted(got) == ["encode_ms.rate", "encode_ms.sat"]
    assert got["encode_ms.sat"] == {"value": 40.0, "unit": "ms"}
    change = dict(parent, **{"engine.tick_lane_rows": 252350.0})
    got = readers.read_all(metrics, {"table": change})
    assert sorted(got) == sorted(n for names in NEW.values() for n in names)
    assert got["tick_lane_pct.rate"] == {"value": 98.0, "unit": "%"}
    # a window without a launch reads nothing, and does not divide by 0
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_each_reads_a_number_in_the_traced_rehearsal_of_its_cell(
        capsys, cell):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7: the step worker's `invalid processed`); the counters and
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
    share = last["metrics"]["tick_lane_pct." + name.rpartition(".")[2]]
    assert 0.0 < share["value"] <= 100.0
