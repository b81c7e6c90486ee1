"""``correct`` has to come out false where a guarantee is broken.

The controls: the plain reference in the program's place (harness/plain.py),
sound and with each guarantee broken, through the whole of run.py.  The
faults: the program itself on the CPU at a cut size (``--dryrun``, which
skips the look for a chip and nothing else), with the timed path broken
underneath: an answer altered where it is produced, a write left out
on one replica, and the device path lost for the rows that carry the load
(``--fault host-plan``).
"""
import json

import pytest

import run as bench_run

CELLS = ["base-1k3.write-rate", "ycsb-a-1k3.mixed-sat"]


def _run(capsys, *argv):
    # at a cut shard count the open-loop cell's own rate offers a handful
    # of writes: the tests offer enough to meet every fault
    rc = bench_run.main(list(argv) + ["--set", "rate_per_s=20000"])
    cap = capsys.readouterr()
    assert rc == 0, cap.err[-2000:]
    last = json.loads(cap.out.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    # every number compared is on the last lines of standard error too
    for name in last["compared"]:
        assert f"compared {name} = " in cap.err
    return last


def _outside(last):
    return sorted(k for k, (v, rel, lim) in last["compared"].items()
                  if lim is not None
                  and not (v <= lim if rel == "<=" else v >= lim))


@pytest.mark.parametrize("cell", CELLS)
def test_the_plain_reference_run_sound_is_correct(capsys, cell):
    last = _run(capsys, "--workload", cell, "--seed", "2147483999",
                "--seconds", "1.5", "--control", "none", "--shards", "16")
    assert last["correct"] is True and _outside(last) == []
    assert last["attempted"] > 50 and last["failed"] == 0
    assert last["compared"]["keys_compared"][0] >= 16


@pytest.mark.parametrize("cell,fault,share,caught_by", [
    # every write of the open loop has its own key and every key is read
    # back, so the chip's own share of 1 % is met here too
    ("base-1k3.write-rate", "drop-acked", 0.01, "lin_mismatch"),
    ("base-1k3.write-rate", "drop-acked", 0.01, "replica_mismatch"),
    ("base-1k3.write-rate", "replica-skip", 0.01, "replica_mismatch"),
    ("ycsb-a-1k3.mixed-sat", "drop-acked", 0.02, "stale_reads"),
    ("ycsb-a-1k3.mixed-sat", "stale-read", 0.02, "stale_reads"),
    ("ycsb-a-1k3.mixed-sat", "replica-skip", 0.02, "replica_mismatch"),
])
def test_a_broken_guarantee_is_not_correct(capsys, cell, fault, share,
                                           caught_by):
    last = _run(capsys, "--workload", cell, "--seed", "2147484001",
                "--seconds", "1.5", "--control", f"{fault}:{share}",
                "--shards", "16")
    assert last["correct"] is False
    assert caught_by in _outside(last)


def _break_lookup(monkeypatch, kv):
    """An answer altered where it is produced: every 20th lookup."""
    real, n = kv.lookup, [0]

    def lookup(self, q):
        got = real(self, q)
        n[0] += 1
        if got is not None and n[0] % 20 == 0:
            return got[:-1] + ("0" if got[-1] != "0" else "1")
        return got

    monkeypatch.setattr(kv, "lookup", lookup)


def _break_one_replica(monkeypatch, kv):
    """Part of the batch left out: replica 3 leaves out every 20th write."""
    real_init, real_update, n = kv.__init__, kv.update, [0]

    def init(self, shard_id, replica_id):
        real_init(self, shard_id, replica_id)
        self.rid = replica_id

    def update(self, entry):
        if self.rid == 3:
            n[0] += 1
            if n[0] % 20 == 0:
                return real_update.__globals__["Result"](value=len(self.d))
        return real_update(self, entry)

    monkeypatch.setattr(kv, "__init__", init)
    monkeypatch.setattr(kv, "update", update)


@pytest.mark.parametrize("cell,breaker,caught_by", [
    ("base-1k3.write-rate", _break_lookup, "lin_mismatch"),
    ("base-1k3.write-rate", _break_one_replica, "replica_mismatch"),
])
def test_the_program_broken_underneath_is_not_correct(
        capsys, monkeypatch, cell, breaker, caught_by):
    from examples import kv_gateway

    breaker(monkeypatch, kv_gateway.KV)
    last = _run(capsys, "--workload", cell, "--seed", "2147484003",
                "--seconds", "2", "--dryrun", "--shards", "8")
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is False
    assert caught_by in _outside(last)


def test_the_device_path_lost_for_the_load_is_not_correct(capsys):
    last = _run(capsys, "--workload", "base-1k3.write-rate", "--seed",
                "2147484004", "--seconds", "2", "--dryrun", "--shards", "8",
                "--fault", "host-plan")
    assert last["correct"] is False
    assert "host_rows_stepped" in _outside(last)
    # every answer was still right: only the counter tells
    assert not {"lin_mismatch", "replica_mismatch"} & set(_outside(last))


def test_the_program_sound_is_correct_on_the_cpu(capsys):
    last = _run(capsys, "--workload", "base-1k3.write-rate", "--seed",
                "2147484005", "--seconds", "2", "--dryrun", "--shards", "8",
                "--trace", "1")
    assert last["correct"] is True, last["compared"]
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "launch_host_ms.rate" in last["metrics"]
    assert "write_p50_ms" not in last["metrics"]    # traced: per-layer only
    # every acknowledged write has its own key and each is read back
    assert last["compared"]["keys_compared"][0] >= last["attempted"]
    assert last["compared"]["reads_compared"][0] >= last["attempted"]


@pytest.mark.parametrize("health", [
    {"host_rows_stepped": None},                        # a check nulled out
    {"retraces": {"num": ["engine.retraces"], "over": "run", "rel": "<=",
                  "limit": 5}},                         # a default changed
    {},                                                 # host rows unheld
    {"host_share": {"num": ["engine.host_rows_stepped"], "over": "window",
                    "rel": "<=", "limit": None}},       # held to no limit
])
def test_a_workload_cannot_loosen_the_health_checks(health):
    with pytest.raises(ValueError):
        bench_run.health_checks({"name": "x", "health": health})


def test_a_counter_that_is_not_there_is_not_correct():
    assert bench_run._within(None, "<=", 0) is False
    assert bench_run._within(None, "<=", None) is True


def test_a_program_switch_in_the_environment_is_refused(capsys, monkeypatch):
    monkeypatch.setenv("DRAGONBOAT_TPU_FUSED_ROUNDS", "1")
    rc = bench_run.main(["--workload", "base-1k3.write-rate", "--seed", "1",
                         "--seconds", "1", "--dryrun"])
    cap = capsys.readouterr()
    assert rc != 0 and cap.out == ""


def test_no_accelerator_is_refused_with_no_result(capsys):
    rc = bench_run.main(["--workload", "base-1k3.write-rate", "--seed", "1",
                         "--seconds", "1"])
    cap = capsys.readouterr()
    assert rc != 0 and cap.out == "" and "no accelerator" in cap.err
