"""PR 35's configuration ``ycsb-a-1k3-snap`` and its cell, as data alone.

The configuration is ``ycsb-a-1k3`` with two numbers of its ``shard``
block changed (``snapshot_entries`` 10, ``compaction_overhead`` 5) and
the keys that describe it; the cell's generator and parameters are its
sibling's, letter for letter, and its health adds two checks of the
snapshot counters; the six new per-layer metrics are ``counter_ratio``
files found by ``index``, read from a table, and fall silent on a program
without the counters (the parent); the rehearsal of the cell on the CPU
at 8 shards is ``correct`` with saves in the window.
"""
import json
import os

import run as bench_run
from harness import readers
from harness.manifest import Manifest, resolve

CONFIG, SIBLING_CONFIG = "ycsb-a-1k3-snap", "ycsb-a-1k3"
CELL, SIBLING = "ycsb-a-1k3-snap.mixed-sat", "ycsb-a-1k3.mixed-sat"
LAYER = "exec engine, WAL, apply (engine/execengine.py, storage/tan.py, rsm/)"
NEW = {
    "snapshot_save_ms.sat": ("ms", "lower"),
    "snapshot_wait_ms.sat": ("ms", "lower"),
    "snapshots_per_commit.sat": ("snapshots", "higher"),
    "snapshot_bytes_per_commit.sat": ("bytes", "lower"),
    "compacted_entries_per_snapshot.sat": ("entries", "higher"),
    "snapshot_streams_per_1k_commits.sat": ("streams", "lower"),
}
DESCRIPTIVE = {"name", "source", "reduced", "published", "assumed",
               "guarantees"}


def test_the_configuration_is_its_sibling_with_two_numbers_changed():
    man = Manifest()
    cfg, sib = man.config(CONFIG), man.config(SIBLING_CONFIG)
    assert "base" not in cfg and cfg["name"] == CONFIG
    differs = {k for k in set(cfg) | set(sib) if cfg.get(k) != sib.get(k)}
    assert differs == (DESCRIPTIVE - {"reduced"}) | {"shard"}
    assert cfg["shard"] == {**sib["shard"], "snapshot_entries": 10,
                            "compaction_overhead": 5}
    assert sib["shard"]["snapshot_entries"] == 0
    own = json.load(open(os.path.join(man.repo_dir,
                                      man.configs[CONFIG]["file"])))
    assert set(own) == DESCRIPTIVE | {"base", "shard"}
    assert own["base"] == SIBLING_CONFIG
    assert cfg["reduced"] == ["recordcount"] == sib["reduced"]
    for key in ("snapshot_entries", "compaction_overhead",
                "snapshot_compression", "snapshot_workers"):
        assert key in cfg["assumed"]
    for key in ("snapshot_entries", "compaction_overhead"):
        assert "UNVERIFIED" in cfg["assumed"][key]
    for key, text in sib["assumed"].items():        # the base's, kept
        assert cfg["assumed"][key] == text
    assert "10 and not a larger interval" in cfg["published"][
        "snapshot_entries"]
    assert cfg["guarantees"][:3] == sib["guarantees"]
    assert len(cfg["guarantees"]) == 4
    assert "streamed snapshot" in cfg["guarantees"][3]
    assert "compacted" in cfg["guarantees"][3]
    entry = man.configs[CONFIG]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    assert [c["name"] for c in man.doc["configs"]].index(CONFIG) > [
        c["name"] for c in man.doc["configs"]].index("ycsb-a-100k357")


def test_the_cell_is_its_sibling_with_the_snapshot_checks_added():
    man = Manifest()
    cell, sib = man.cell(CELL), man.cell(SIBLING)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["generator"] == sib["generator"]
    assert cell["params"] == sib["params"]          # letter for letter
    assert "op_timeout_s" not in cell["params"]     # the default, 60 s
    health = bench_run.health_checks(cell)
    sib_health = bench_run.health_checks(sib)
    assert set(health) - set(sib_health) == {
        "snapshot_saves_per_write", "snapshot_failures"}
    assert all(health[k] == v for k, v in sib_health.items())
    assert health["host_steps_per_op"]["limit"] == 0.25
    assert health["snapshot_saves_per_write"] == {
        "num": ["engine.snapshots_saved"], "den": ["gateway.committed"],
        "over": "window", "rel": ">=", "limit": 0.15}
    assert health["snapshot_failures"] == {
        "num": ["engine.snapshot_failures"], "over": "run", "rel": "<=",
        "limit": 0}
    names = [w["name"] for w in man.doc["workloads"]]
    assert names.index(CELL) > names.index("ycsb-a-100k357.churn-sat")
    assert len(man.cells[CELL]["why"]) <= 200
    # end to end: the sibling's metrics, under the bounds they have
    assert [m["name"] for m in man.end_to_end(CELL)] == [
        m["name"] for m in man.end_to_end(SIBLING)]
    # per layer: everything the sibling reads, and the six besides
    have = [m["name"] for m in man.per_layer(CELL)]
    assert [n for n in have if n not in NEW] == [
        m["name"] for m in man.per_layer(SIBLING)]
    assert [n for n in have if n in NEW] == list(NEW)


def test_six_entries_each_with_a_file_a_reader_and_its_cell():
    man = Manifest()
    by_name = {m["name"]: m for m in man.per_layer(CELL)}
    names = [m["name"] for m in man.doc["per_layer"]]
    for name, (unit, better) in NEW.items():
        m = by_name[name]
        assert os.path.isfile(os.path.join(
            man.bench_dir, "layers", name + ".json"))
        assert m["reader"] == "harness.readers.counter_ratio"
        assert callable(resolve(m["reader"]))
        assert CELL in m["workloads"]       # `in`: a later cell may follow
        assert SIBLING not in m["workloads"]
        assert m["source"] == "program_counter"
        assert m["layer"] == LAYER and m["moves"] == "ops_per_s"
        assert (m["unit"], m["better"]) == (unit, better)
        # by `index`, never a slice from the end: a later entry goes
        # behind these
        assert names.index(name) > names.index("gw_timed_wake_pct.sat")
        assert names.count(name) == 1
    assert not set(NEW) & {m["name"] for m in man.per_layer(SIBLING)}


def test_the_metrics_are_left_out_where_the_program_has_no_such_counters():
    man = Manifest()
    metrics = [m for m in man.per_layer(CELL) if m["name"] in NEW]
    assert len(metrics) == 6
    parent = {"gateway.committed": 10000.0, "engine.launches": 250.0}
    assert readers.read_all(metrics, {"table": parent}) == {}
    change = dict(parent, **{
        "engine.snapshots_saved": 3000.0, "engine.t_snapshot_save_ms": 45000.0,
        "engine.t_snapshot_wait_ms": 1500.0, "engine.snapshot_bytes": 33.0e6,
        "engine.log_entries_compacted": 30300.0,
        "engine.snapshots_streamed": 5.0})
    got = {k: v["value"]
           for k, v in readers.read_all(metrics, {"table": change}).items()}
    assert got == {
        "snapshot_save_ms.sat": 15.0, "snapshot_wait_ms.sat": 0.5,
        "snapshots_per_commit.sat": 0.3,
        "snapshot_bytes_per_commit.sat": 3300.0,
        "compacted_entries_per_snapshot.sat": 10.1,
        "snapshot_streams_per_1k_commits.sat": 0.5}
    # no stream at all is a reading, 0, and not a silence
    quiet = dict(change, **{"engine.snapshots_streamed": 0.0})
    assert readers.read_all(metrics, {"table": quiet})[
        "snapshot_streams_per_1k_commits.sat"]["value"] == 0.0
    # a window without a save reads nothing per save, and divides by no 0
    idle = dict(change, **{"engine.snapshots_saved": 0.0})
    assert "snapshot_save_ms.sat" not in readers.read_all(
        metrics, {"table": idle})


def test_the_rehearsal_on_the_cpu_is_correct_with_saves_in_the_window(capsys):
    # at 8 shards a group holds 1,250 records, so the load's 3,000 saves
    # write 1.3 MB each (11 KB at 1,000 shards): on a loaded box the load
    # outlasts the 60 s it borrows from `op_timeout_s`
    rc = bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "4",
                         "--trace", "1", "--dryrun", "--shards", "8",
                         "--set", "op_timeout_s=240"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    lines = cap.out.strip().splitlines()
    last, diag = json.loads(lines[-1]), json.loads(lines[-2])["diag"]
    outside = sorted(k for k, (v, rel, lim) in last["compared"].items()
                     if lim is not None
                     and not (v <= lim if rel == "<=" else v >= lim))
    assert last["correct"], outside
    assert diag["window_delta"]["engine.snapshots_saved"] > 0
    assert last["compared"]["snapshot_failures"][0] == 0
    assert last["compared"]["snapshot_saves_per_write"][0] >= 0.15
    for name in NEW:
        assert name in last["metrics"], sorted(last["metrics"])
    assert 0.15 <= last["metrics"]["snapshots_per_commit.sat"]["value"] <= 0.34
    assert last["metrics"]["compacted_entries_per_snapshot.sat"][
        "value"] >= 5.0
