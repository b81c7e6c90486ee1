"""PR 28's configuration ``ycsb-a-10k5`` and its cell, as new files alone.

The manifest resolves the configuration through both of its bases; the
cell rehearsed on the CPU at 8 shards x 5 replicas prints every declared
metric of both kinds that the CPU can give, the three new ones among
them, and leaves no directory behind; the controls with five replicas
come out not ``correct`` (``replica-skip`` now skips the fifth); the new
readers leave their metric out where the program lacks the counter, as
the parent does; the open-file limit is met before any replica starts.
"""
import json
import os
import resource
import tempfile

import pytest

import run as bench_run
from deployments import ondisk
from harness import readers
from harness.manifest import Manifest, resolve

CELL = "ycsb-a-10k5.mixed-sat"
SIBLING = "ycsb-a-1k3.mixed-sat"
NEW = ["sm_update_us_per_entry.sat", "sm_wal_bytes_per_commit.sat",
       "leader_changes_per_min.sat"]
# read from the device's trace: a CPU trace has no such program
DEVICE_ONLY = {"step_roofline_pct.sat"}


def _last(capsys, *argv):
    rc = bench_run.main(list(argv))
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    lines = cap.out.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["diag"]


def _rehearse(capsys, *argv):
    """``_last`` of a run of the program, once more if its load phase
    failed: ``ThreadsClosed.load`` sends each record once, and at 8 x 5
    on the CPU one load in six meets an election that drops the ~9
    proposals its shard had in flight (PERF.md section 7)."""
    try:
        return _last(capsys, *argv)
    except RuntimeError as e:
        if "load phase" not in str(e):
            raise
        capsys.readouterr()
        return _last(capsys, *argv)


def _outside(last):
    return sorted(k for k, (v, rel, lim) in last["compared"].items()
                  if lim is not None
                  and not (v <= lim if rel == "<=" else v >= lim))


def test_the_manifest_resolves_the_configuration_through_both_bases():
    man = Manifest()
    cfg = man.config("ycsb-a-10k5")
    base, ycsb = man.config("base-1k3"), man.config("ycsb-a-1k3")
    assert "base" not in cfg and cfg["name"] == "ycsb-a-10k5"
    for key in ("nodehost", "shard", "gateway"):      # from base-1k3
        assert cfg[key] == base[key]
    assert cfg["records"] == ycsb["records"]          # from ycsb-a-1k3
    assert cfg["cluster"] == {**base["cluster"], "shards": 1000,
                              "replicas": 5, "nodehosts": 5}
    assert cfg["engine"] == {**base["engine"], "capacity": 8192, "P": 5}
    assert cfg["reduced"] == ["shards", "recordcount"]
    assert cfg["published"]["shards"] == 10_000
    assert len(cfg["guarantees"]) == 3 and "five" in cfg["guarantees"][2]
    assert cfg["reference"].startswith("harness.reference.compare")
    entry = man.configs["ycsb-a-10k5"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    # the state machine is the program's factory; the deployment roots it
    make = resolve(cfg["state_machine"])
    assert resolve(cfg["deployment"]) is ondisk.OnDiskDeployment
    with tempfile.TemporaryDirectory() as root:
        assert make(root)(3, 2).dir == os.path.join(root, "3-2")


def test_the_cell_is_its_sibling_on_another_deployment():
    man = Manifest()
    cell, sib = man.cell(CELL), man.cell(SIBLING)
    assert cell["chips"] == 1 and cell["config"] == "ycsb-a-10k5"
    for key in ("generator", "health"):
        assert cell[key] == sib[key]
    # the traffic is the sibling's, parameter for parameter; the one key
    # more is a deadline, not load: ThreadsClosed.load borrows its own
    # from op_timeout_s, and the default 60 s is too near what this load
    # phase takes (17-35 s: 80 MB through the chip machine's 9p, whose
    # speed is the shared host's; 60+ s at twice the groups).  Twice the
    # default and no more: an operation lost to a change of leader waits
    # its whole timeout out (PERF.md section 7)
    params = dict(cell["params"])
    assert params.pop("op_timeout_s") == 120
    assert params == sib["params"] and "op_timeout_s" not in sib["params"]
    # the read-back is cut to a sample from the seed, never under 2,048
    assert cell["compare"]["linearizable_sample"] >= 2048
    names = lambda ms: [m["name"] for m in ms]  # noqa: E731
    assert names(man.end_to_end(CELL)) == names(man.end_to_end(SIBLING))
    assert set(names(man.per_layer(CELL))) == (
        set(names(man.per_layer(SIBLING))) | set(NEW))
    by_name = {m["name"]: m for m in man.per_layer(CELL)}
    for name in NEW:
        m = by_name[name]
        assert m["reader"] == "harness.readers.counter_ratio"
        assert m["source"] == "program_counter" and m["moves"] == "ops_per_s"
        assert CELL in m["workloads"]
    assert by_name["sm_wal_bytes_per_commit.sat"]["workloads"] == [CELL]


def test_the_new_readers_leave_their_metric_out_on_the_parent():
    man = Manifest()
    metrics = [m for m in man.per_layer(CELL) if m["name"] in NEW]
    parent = {"engine.apply_entries": 5000.0, "gateway.committed": 1000.0,
              "loadgen.window_s": 20.0}
    assert readers.read_all(metrics, {"table": parent}) == {}
    change = dict(parent, **{"engine.t_sm_update_ms": 100.0,
                             "engine.sm_wal_bytes": 5_200_000.0,
                             "engine.leader_changes": 0.0})
    got = readers.read_all(metrics, {"table": change})
    assert got == {
        "sm_update_us_per_entry.sat": {"value": 20.0, "unit": "us"},
        "sm_wal_bytes_per_commit.sat": {"value": 5200.0, "unit": "bytes"},
        "leader_changes_per_min.sat": {"value": 0.0, "unit": "changes/min"},
    }
    got = readers.read_all(metrics, {"table": dict(
        change, **{"engine.leader_changes": 10.0})})
    assert got["leader_changes_per_min.sat"]["value"] == 30.0
    # an idle window divides by nothing
    idle = {k: 0.0 for k in change}
    assert readers.read_all(metrics, {"table": idle}) == {}


def test_update_latency_counts_the_windows_proposals_and_what_never_came():
    class Fut:
        def __init__(self, t_done=None):
            self.t_done = t_done

        def result(self, timeout):
            if self.t_done is None:
                raise TimeoutError("still out")

    log = [(9.0, Fut(9.5)),                       # before the window
           *[(10.0 + i, Fut(10.0 + i + 0.1 * (i + 1))) for i in range(8)],
           (18.5, Fut()), (19.0, Fut()),          # never acknowledged
           (20.0, Fut(20.1))]                     # after it
    got = ondisk.update_latency_ms(log, 10.0, 20.0)
    assert (got["n"], got["missing"]) == (8, 2)
    assert got["p50"] == pytest.approx(500.0)
    assert got["p95"] is None and got["p99"] is None   # among the missing
    assert ondisk.update_latency_ms(log, 30.0, 40.0) is None


def test_the_rehearsal_prints_every_metric_and_leaves_no_directory(
        capsys, tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", None)
    man = Manifest()
    argv = ["--workload", CELL, "--seed", "2147484028", "--seconds", "4",
            "--dryrun", "--shards", "8",
            # 8 of 1,000 shards leave the load phase 16 in flight; the
            # rehearsal is of the code, so it loads with 64
            "--set", "load_inflight=8000"]
    traced, diag = _rehearse(capsys, *argv, "--trace", "1")
    want = {m["name"] for m in man.per_layer(CELL)} - DEVICE_ONLY
    assert want <= set(traced["metrics"]), want - set(traced["metrics"])
    for name in NEW:
        assert traced["metrics"][name]["value"] >= 0.0
    # five replicas append every 1 KB write to their own logs: more than
    # four replicas' worth a commit (applies that trail their commit across
    # the edges of a 4 s window take a few per cent off the chip's 5,234)
    assert traced["metrics"]["sm_wal_bytes_per_commit.sat"]["value"] > 4 * 1100
    assert traced["device"]["platform"] == "cpu" and diag["shards"] == 8
    assert diag["setup_split_s"]["sm_open_s"] > 0.0
    assert diag["setup_split_s"]["boot_s"] > 0.0
    assert diag["fd_soft_limit"] >= 8 * 5 + ondisk.FD_HEADROOM
    # the closed loop's update latency, over the window's proposals
    upd = diag["update_latency_ms"]
    # (run.py reads the counters just outside the generator's own window)
    assert abs(upd["n"] + upd["missing"]
               - diag["loadgen"]["loadgen.writes_attempted"]) <= 2 * 64
    assert upd["n"] > 0 and 0.0 < upd["p50"] <= upd["p95"]
    # not held to `correct`: at this size on the CPU the step worker's
    # `invalid processed` (PERF.md section 7) can strike, as in the
    # sibling's rehearsal; the files, counters and readers are rehearsed
    for name in ("retraces", "leaked_threads"):
        assert name not in _outside(traced), traced["compared"]
    assert traced["compared"]["keys_compared"][0] == 10_000
    untraced, _ = _rehearse(capsys, *argv, "--trace", "0")
    assert set(untraced["metrics"]) == {
        m["name"] for m in man.end_to_end(CELL)}
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("fault,caught_by", [
    ("drop-acked", "stale_reads"),
    ("stale-read", "stale_reads"),
    ("replica-skip", "replica_mismatch"),
])
def test_a_broken_guarantee_is_not_correct_with_five_replicas(
        capsys, fault, caught_by):
    last, diag = _last(capsys, "--workload", CELL, "--seed", "2147484029",
                       "--seconds", "1.5", "--control", f"{fault}:0.02",
                       "--shards", "16")
    assert diag["shards"] == 16
    assert last["correct"] is False and caught_by in _outside(last)


def test_the_control_run_sound_is_correct_with_five_replicas(capsys):
    last, _ = _last(capsys, "--workload", CELL, "--seed", "2147484030",
                    "--seconds", "1.5", "--control", "none", "--shards", "16")
    assert last["correct"] is True and _outside(last) == []


def test_replica_skip_skips_the_fifth():
    from harness import plain

    cfg = Manifest().config("ycsb-a-10k5")
    pc = plain.PlainCluster(cfg, 16, "replica-skip", 1.0, seed=1)
    assert pc.replicas == [1, 2, 3, 4, 5]
    pc.handle(3).propose(b"k=v").result()
    held = [pc.replica_read(r, 3, "k") for r in pc.replicas]
    assert held == ["v", "v", "v", "v", None]


def test_the_open_file_limit_is_met_or_the_build_stops_with_a_sentence(
        monkeypatch):
    limits = {"now": (1024, 4096)}
    monkeypatch.setattr(resource, "getrlimit", lambda _r: limits["now"])
    monkeypatch.setattr(resource, "setrlimit",
                        lambda _r, lim: limits.update(now=lim))
    assert ondisk.raise_fd_limit(512) == 1024 and limits["now"][0] == 1024
    assert ondisk.raise_fd_limit(3000) == 3000
    assert limits["now"] == (3000, 4096)
    with pytest.raises(RuntimeError, match="hard open-file limit is 4096"):
        ondisk.raise_fd_limit(10_512)
    limits["now"] = (resource.RLIM_INFINITY, resource.RLIM_INFINITY)
    assert ondisk.raise_fd_limit(10_512) == resource.RLIM_INFINITY
