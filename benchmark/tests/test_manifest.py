"""A cell, a configuration and a counter_ratio metric arrive as new files
plus new BENCHMARK.json entries; no file that is there is edited."""
import json
import os
import shutil

from harness import readers
from harness.manifest import REPO_DIR, Manifest, resolve


def test_the_shipped_manifest_loads_every_cell():
    man = Manifest()
    assert man.cells
    for name in man.cells:
        cell = man.cell(name)
        cfg = man.config(cell["config"])
        assert cfg["name"] == cell["config"]
        for key in ("source", "reduced", "assumed", "guarantees"):
            assert key in cfg
        names = [m["name"] for m in man.end_to_end(name)]
        assert "setup_s" in names and len(names) >= 2
        assert man.per_layer(name)
        for m in man.end_to_end(name) + man.per_layer(name):
            assert callable(resolve(m["reader"]))
        assert callable(resolve(cell["generator"]))
        assert callable(resolve(cfg["deployment"]))


def test_a_configuration_lies_over_the_one_it_names_as_its_base():
    man = Manifest()
    base, ycsb = man.config("base-1k3"), man.config("ycsb-a-1k3")
    assert "base" not in ycsb and ycsb["name"] == "ycsb-a-1k3"
    for key in ("cluster", "engine", "nodehost", "shard", "gateway",
                "state_machine", "guarantees", "deployment"):
        assert ycsb[key] == base[key]
    assert ycsb["records"]["recordcount"] == 10_000
    assert ycsb["reduced"] == ["recordcount"] and base["reduced"] == []


def test_new_cell_config_and_metric_are_found_as_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO_DIR, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = json.load(open(os.path.join(REPO_DIR, "BENCHMARK.json")))
    before = {p: open(p).read() for p in
              (str(x) for x in (root / "benchmark").rglob("*.json"))}

    old = doc["configs"][0]
    cfg = json.load(open(os.path.join(REPO_DIR, old["file"])))
    cfg["name"] = "other-geometry"
    cfg["engine"]["capacity"] = 65536
    (root / "benchmark/configs/other-geometry.json").write_text(
        json.dumps(cfg))
    doc["configs"].append({**old, "name": "other-geometry",
                           "file": "benchmark/configs/other-geometry.json"})
    wl = json.load(open(os.path.join(
        REPO_DIR, "benchmark/workloads", doc["workloads"][0]["name"] + ".json")))
    wl["config"] = "other-geometry"
    (root / "benchmark/workloads/other-geometry.burst.json").write_text(
        json.dumps(wl))
    doc["workloads"].append({
        "name": "other-geometry.burst", "config": "other-geometry",
        "traffic": "burst", "chips": 1, "why": "a later PR's cell"})
    (root / "benchmark/layers/wal_flushes_per_op.json").write_text(json.dumps({
        "reader": "harness.readers.counter_ratio",
        "num": ["engine.wal_flushes"],
        "den": ["loadgen.acked"]}))
    doc["per_layer"].append({
        "name": "wal_flushes_per_op", "unit": "1/op", "better": "lower",
        "source": "program_counter", "layer": "WAL", "moves": "ops_per_s",
        "workloads": ["other-geometry.burst"]})
    for m in doc["end_to_end"]:
        if m["name"] == "ops_per_s":
            m["workloads"].append("other-geometry.burst")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    man = Manifest(str(root))
    cell = man.cell("other-geometry.burst")
    assert cell["generator"] and cell["config"] == "other-geometry"
    assert man.config("other-geometry")["engine"]["capacity"] == 65536
    layer = [m for m in man.per_layer("other-geometry.burst")
             if m["name"] == "wal_flushes_per_op"]
    assert len(layer) == 1
    assert "ops_per_s" in [m["name"] for m in
                           man.end_to_end("other-geometry.burst")]
    ctx = {"table": {"engine.wal_flushes": 30, "loadgen.acked": 120}}
    assert readers.read_all(layer, ctx) == {
        "wal_flushes_per_op": {"value": 0.25, "unit": "1/op"}}
    # a counter the program does not have yet: the metric stays silent
    assert readers.read_all(layer, {"table": {"loadgen.acked": 120}}) == {}
    # and nothing that was there was touched
    assert before == {p: open(p).read() for p in before}
    # the older cells do not report the new metric
    first = doc["workloads"][0]["name"]
    assert "wal_flushes_per_op" not in [
        m["name"] for m in man.per_layer(first)]
