"""The six per-layer metrics of PR 36 (what a launch asks of the device,
by the call: ``device_puts_per_launch`` = ``engine.device_puts`` /
``engine.launches``, the host-to-device transfers a launch;
``device_programs_per_launch`` = ``engine.device_programs`` /
``engine.launches``, the programs it enqueues; ``dispatch_ms`` =
``engine.t_dispatch_ms`` / ``engine.launches``, the host's time to
enqueue the wave's programs, a counter that is there since PR 26) are
data alone: a ``counter_ratio`` file each and a ``per_layer`` entry.  The
``.sat`` ones move ``ops_per_s`` in the four saturated cells, the
``.rate`` ones ``write_p95_ms`` in the open loop.  Each has its file,
resolves, and reads a number in the traced rehearsal of a cell on the CPU
at 8 shards; where the program has no ``device_*`` counter, as the parent
has not, those four are left out and nothing raises.
"""
import json
import os

import pytest

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

BASES = {"device_puts_per_launch": ("puts", "engine.device_puts"),
         "device_programs_per_launch": ("programs", "engine.device_programs"),
         "dispatch_ms": ("ms", "engine.t_dispatch_ms")}
SAT = ["ycsb-a-1k3.mixed-sat", "ycsb-a-10k5.mixed-sat",
       "ycsb-a-100k357.churn-sat", "ycsb-a-1k3-snap.mixed-sat"]
RATE = ["base-1k3.write-rate"]
KINDS = {".sat": (SAT, "ops_per_s"), ".rate": (RATE, "write_p95_ms")}
LAYER = ("colocated engine, host side (ops/colocated.py, ops/engine.py, "
         "ops/hostplane.py)")


@pytest.mark.parametrize("suffix", sorted(KINDS))
@pytest.mark.parametrize("base", sorted(BASES))
def test_an_entry_with_a_file_a_reader_and_its_cells(base, suffix):
    man = Manifest()
    name = base + suffix
    unit, num = BASES[base]
    cells, moves = KINDS[suffix]
    for cell in cells:
        m = {m["name"]: m for m in man.per_layer(cell)}[name]
        assert m["reader"] == "harness.readers.counter_ratio"
        assert callable(resolve(m["reader"]))
        assert (m["num"], m["den"]) == ([num], ["engine.launches"])
        # `in`, not `==`: a later cell may be appended to the list
        assert cell in m["workloads"]
        assert m["source"] == "program_counter"
        assert m["layer"] == LAYER and m["moves"] == moves
        assert (m["unit"], m["better"]) == (unit, "lower")
    assert os.path.isfile(os.path.join(
        man.bench_dir, "layers", name + ".json"))
    # a cell of the other kind reports no such end-to-end metric
    other = RATE if suffix == ".sat" else SAT
    assert name not in {m["name"] for m in man.per_layer(other[0])}
    # after everything that was there (`index`, not a slice from the
    # end: a later entry goes behind these)
    names = [m["name"] for m in man.doc["per_layer"]]
    assert names.index(name) > names.index(
        "snapshot_streams_per_1k_commits.sat")
    assert names.count(name) == 1


def test_the_device_counts_are_left_out_where_the_program_has_none():
    man = Manifest()
    mine = {b + ".sat" for b in BASES}
    metrics = [m for m in man.per_layer(SAT[3]) if m["name"] in mine]
    assert len(metrics) == 3
    # the parent: the dispatch phase is counted, the device calls are not
    parent = {"engine.launches": 200.0, "engine.t_dispatch_ms": 3200.0}
    assert readers.read_all(metrics, {"table": parent}) == {
        "dispatch_ms.sat": {"value": 16.0, "unit": "ms"}}
    change = dict(parent, **{"engine.device_puts": 200.0,
                             "engine.device_programs": 1970.0})
    assert readers.read_all(metrics, {"table": change}) == {
        "dispatch_ms.sat": {"value": 16.0, "unit": "ms"},
        "device_puts_per_launch.sat": {"value": 1.0, "unit": "puts"},
        "device_programs_per_launch.sat": {"value": 9.85,
                                           "unit": "programs"}}
    # a window with no launch reads nothing, and does not divide by 0
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


@pytest.mark.parametrize("cell,suffix", [(SAT[0], ".sat"),
                                         (RATE[0], ".rate")])
def test_they_read_numbers_in_the_traced_rehearsal_of_a_cell(
        cell, suffix, capsys):
    # the YCSB rehearsal is not correct on the CPU at this size (PERF.md
    # section 7: `host_steps_per_op` over its limit); the counters and
    # the reader are what is rehearsed here
    rc = bench_run.main(["--workload", cell, "--seed", "1", "--seconds",
                         "4", "--trace", "1", "--dryrun", "--shards", "8"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    got = {b: last["metrics"].get(b + suffix) for b in BASES}
    assert all(v is not None for v in got.values()), sorted(last["metrics"])
    # one upload a launch, and a row upload or a table rebuild now and
    # then: set-up's are before the window.  The window's edges cut a
    # launch between its upload and its count, so not 1.0 to the digit
    assert 0.95 <= got["device_puts_per_launch"]["value"] <= 2.0
    # 1 + 3 a single round, 1 + 9 a K = 3 wave
    assert 3.8 <= got["device_programs_per_launch"]["value"] <= 10.5
    assert got["dispatch_ms"]["value"] > 0.0
