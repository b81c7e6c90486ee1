"""The two per-layer metrics of PR 38 (``device_arrays_per_launch`` =
``engine.device_arrays`` / ``engine.launches``: the arrays a launch hands
the runtime, one a put commits and each one a program takes or returns)
are data alone: a ``counter_ratio`` file each and a ``per_layer`` entry.
The ``.sat`` one moves ``ops_per_s`` in the four saturated cells, the
``.rate`` one ``write_p95_ms`` in the open loop, in the cells of
``dispatch_ms``.  Each has its file, resolves, and reads a number in the
traced rehearsal of a cell on the CPU at 8 shards; where the program has
no ``device_arrays`` counter, as the parent has not, it is left out and
nothing raises.
"""
import json
import os

import pytest

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

NAME = "device_arrays_per_launch"
SAT = ["ycsb-a-1k3.mixed-sat", "ycsb-a-10k5.mixed-sat",
       "ycsb-a-100k357.churn-sat", "ycsb-a-1k3-snap.mixed-sat"]
RATE = ["base-1k3.write-rate"]
KINDS = {".sat": (SAT, "ops_per_s"), ".rate": (RATE, "write_p95_ms")}
LAYER = ("colocated engine, host side (ops/colocated.py, ops/engine.py, "
         "ops/hostplane.py)")


@pytest.mark.parametrize("suffix", sorted(KINDS))
def test_an_entry_with_a_file_a_reader_and_its_cells(suffix):
    man = Manifest()
    name = NAME + suffix
    cells, moves = KINDS[suffix]
    for cell in cells:
        m = {m["name"]: m for m in man.per_layer(cell)}[name]
        assert m["reader"] == "harness.readers.counter_ratio"
        assert callable(resolve(m["reader"]))
        assert (m["num"], m["den"]) == (["engine.device_arrays"],
                                        ["engine.launches"])
        # `in`, not `==`: a later cell may be appended to the list
        assert cell in m["workloads"]
        assert m["source"] == "program_counter"
        assert m["layer"] == LAYER and m["moves"] == moves
        assert (m["unit"], m["better"]) == ("arrays", "lower")
    assert os.path.isfile(os.path.join(
        man.bench_dir, "layers", name + ".json"))
    other = RATE if suffix == ".sat" else SAT
    assert name not in {m["name"] for m in man.per_layer(other[0])}
    # the cells of the phase the count explains
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    assert by_name[name]["workloads"] == by_name[
        "dispatch_ms" + suffix]["workloads"]
    # after everything that was there (`index`, not a slice from the
    # end: a later entry goes behind these)
    names = [m["name"] for m in man.doc["per_layer"]]
    assert names.index(name) > names.index(
        "device_programs_per_launch" + suffix)
    assert names.index(name) > names.index(
        "snapshot_save_cpu_ms.sat")
    assert names.count(name) == 1


@pytest.mark.parametrize("cell,suffix", [(SAT[0], ".sat"),
                                         (RATE[0], ".rate")])
def test_the_count_is_left_out_where_the_program_has_none(cell, suffix):
    man = Manifest()
    metrics = [m for m in man.per_layer(cell) if m["name"] == NAME + suffix]
    assert len(metrics) == 1
    parent = {"engine.launches": 200.0, "engine.device_programs": 2000.0}
    assert readers.read_all(metrics, {"table": parent}) == {}
    change = dict(parent, **{"engine.device_arrays": 32200.0})
    assert readers.read_all(metrics, {"table": change}) == {
        NAME + suffix: {"value": 161.0, "unit": "arrays"}}
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


def test_it_reads_a_number_in_the_traced_rehearsal_of_a_cell(capsys):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7); the counter and the reader are what is rehearsed here
    rc = bench_run.main(["--workload", SAT[0], "--seed", "1", "--seconds",
                         "4", "--trace", "1", "--dryrun", "--shards", "8"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    got = last["metrics"].get(NAME + ".sat")
    assert got is not None, sorted(last["metrics"])
    # 5 + 52 a single round, 5 + 3 x 52 a K = 3 wave (tests/
    # test_launch_upload.py); a row upload or a fallback gather now and
    # then, and the window's edges cut a launch
    assert 50.0 <= got["value"] <= 200.0, got
