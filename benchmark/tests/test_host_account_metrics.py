"""The twenty per-layer metrics of PR 26 (the host's account: launch wall
and gap, active rows, persist, WAL, apply, the gateway's queue and poll,
why reads leave the lease) are data alone: a ``counter_ratio`` file each
and a ``per_layer`` entry.  Each has its file, resolves, and reads a
number in the traced rehearsal of its cell on the CPU at 8 shards."""
import json
import os

import pytest

import run as bench_run
from harness.manifest import Manifest, resolve

NEW = {
    "base-1k3.write-rate": [
        "gw_queue_wait_ms.rate", "gw_ack_lag_ms.rate", "launch_wall_ms.rate",
        "launch_gap_ms.rate", "active_rows_per_launch.rate",
        "persist_ms.rate", "wal_appends_per_commit.rate",
        "apply_wait_ms.rate",
    ],
    "ycsb-a-1k3.mixed-sat": [
        "gw_queue_wait_ms.sat", "gw_ack_lag_ms.sat", "gw_polls_per_op.sat",
        "lease_miss_apply_lag_pct", "launch_wall_ms.sat",
        "launch_gap_ms.sat", "active_rows_per_launch.sat", "persist_ms.sat",
        "wal_appends_per_commit.sat", "wal_bytes_per_commit.sat",
        "apply_wait_ms.sat", "apply_busy_pct.sat",
    ],
}
LAYERS = {
    "client front (gateway/gateway.py)",
    "colocated engine, host side (ops/colocated.py, ops/engine.py, "
    "ops/hostplane.py)",
    "exec engine, WAL, apply (engine/execengine.py, storage/tan.py, rsm/)",
}


def test_twenty_entries_each_with_a_file_a_reader_and_one_cell():
    man = Manifest()
    assert sum(len(v) for v in NEW.values()) == 20
    for cell, names in NEW.items():
        by_name = {m["name"]: m for m in man.per_layer(cell)}
        e2e = {m["name"] for m in man.end_to_end(cell)}
        for name in names:
            m = by_name[name]
            assert os.path.isfile(os.path.join(
                man.bench_dir, "layers", name + ".json"))
            assert m["reader"] == "harness.readers.counter_ratio"
            assert callable(resolve(m["reader"]))
            assert m["workloads"] == [cell]
            assert m["source"] == "program_counter"
            assert m["layer"] in LAYERS and m["moves"] in e2e


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
