"""The four per-layer metrics of the input hint (``pulled_rows_per_launch``
= ``engine.input_rows_pulled`` / ``engine.launches``: the rows a launch
carried for their queued input alone, neither the caller's nor the
coalesce scan's; ``input_wait_ms`` = ``engine.t_input_wait_ms`` /
``engine.input_waits``: from a row's first queued input to the drain
that took it) are data alone: a ``counter_ratio`` file each and a
``per_layer`` entry.  The ``.sat`` ones move ``ops_per_s`` in the five
saturated cells, the ``.rate`` ones ``write_p95_ms`` in the open loop.
Each has its file, resolves, and reads a number in the traced rehearsal
of a cell on the CPU at 8 shards; where the program has no such
counter, as the parent has not, it is left out and nothing raises.
"""
import json
import os

import pytest

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

SAT = ["ycsb-a-1k3.mixed-sat", "ycsb-a-10k5.mixed-sat",
       "ycsb-a-100k357.churn-sat", "ycsb-a-1k3-snap.mixed-sat",
       "ycsb-b-1k3-fr.mixed-sat"]
RATE = ["base-1k3.write-rate"]
KINDS = {".sat": (SAT, "ops_per_s"), ".rate": (RATE, "write_p95_ms")}
METRICS = {
    "pulled_rows_per_launch": (["engine.input_rows_pulled"],
                               ["engine.launches"], "rows", "higher"),
    "input_wait_ms": (["engine.t_input_wait_ms"], ["engine.input_waits"],
                      "ms", "lower"),
}
LAYER = ("colocated engine, host side (ops/colocated.py, ops/engine.py, "
         "ops/hostplane.py)")


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("suffix", sorted(KINDS))
def test_an_entry_with_a_file_a_reader_and_its_cells(name, suffix):
    man = Manifest()
    full = name + suffix
    cells, moves = KINDS[suffix]
    num, den, unit, better = METRICS[name]
    for cell in cells:
        m = {m["name"]: m for m in man.per_layer(cell)}[full]
        assert m["reader"] == "harness.readers.counter_ratio"
        assert callable(resolve(m["reader"]))
        assert (m["num"], m["den"]) == (num, den)
        # `in`, not `==`: a later cell may be appended to the list
        assert cell in m["workloads"]
        assert m["source"] == "program_counter"
        assert m["layer"] == LAYER and m["moves"] == moves
        assert (m["unit"], m["better"]) == (unit, better)
    assert os.path.isfile(os.path.join(
        man.bench_dir, "layers", full + ".json"))
    other = RATE if suffix == ".sat" else SAT
    assert full not in {m["name"] for m in man.per_layer(other[0])}
    # the cells of the launch whose rows it counts
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    cousin = "ops_per_launch.sat" if suffix == ".sat" else "dispatch_ms.rate"
    assert set(cells) <= set(by_name[cousin]["workloads"])
    # after everything that was there (`index`, not a slice from the
    # end: a later entry goes behind these)
    names = [m["name"] for m in man.doc["per_layer"]]
    for older in ("read_apply_wait_ms.sat", "read_confirm_ms.sat",
                  "device_arrays_per_launch" + suffix):
        assert names.index(full) > names.index(older)
    assert names.count(full) == 1


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("cell,suffix", [(SAT[0], ".sat"),
                                         (RATE[0], ".rate")])
def test_the_metric_is_left_out_where_the_program_has_none(name, cell,
                                                          suffix):
    man = Manifest()
    metrics = [m for m in man.per_layer(cell) if m["name"] == name + suffix]
    assert len(metrics) == 1
    num, den, unit, _ = METRICS[name]
    parent = {"engine.launches": 200.0, "engine.coalesced_rows": 900.0}
    assert readers.read_all(metrics, {"table": parent}) == {}
    change = dict(parent, **{num[0]: 1500.0, den[0]: 50.0})
    if den[0] == "engine.launches":
        change[den[0]] = 200.0
    assert readers.read_all(metrics, {"table": change}) == {
        name + suffix: {"value": 1500.0 / change[den[0]], "unit": unit}}
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


def test_they_read_a_number_in_the_traced_rehearsal_of_a_cell(capsys):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7); the counters and the readers are what is rehearsed
    rc = bench_run.main(["--workload", SAT[0], "--seed", "1", "--seconds",
                         "4", "--trace", "1", "--dryrun", "--shards", "8"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    wait = last["metrics"].get("input_wait_ms.sat")
    assert wait is not None, sorted(last["metrics"])
    assert wait["value"] > 0.0, wait
    # a launch pulls a row only when another member's replica has input
    # queued and the launch skips the scan: at 8 shards it may read 0,
    # and then the ratio is 0, never absent while launches ran
    pulled = last["metrics"].get("pulled_rows_per_launch.sat")
    assert pulled is not None and pulled["value"] >= 0.0, pulled
