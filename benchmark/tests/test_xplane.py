"""The trace reduction, on a small trace recorded on the v5e (PR 25:
``base-1k3.write-rate``, half a second, python tracer off)."""
import gzip
import os

import pytest

from harness import readers, xplane
from harness.manifest import Manifest

TRACE_GZ = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb.gz")
LABELS = ("raft-colocated-step", "raft-colocated-select")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    pb = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(TRACE_GZ) as src:
        pb.write_bytes(src.read())
    return xplane.reduce_xplane(str(pb), LABELS)


def test_busy_window_and_programs(reduced):
    r = reduced
    assert r["devices"] == 1
    assert 0.2 < r["window_s"] < 2.0
    assert 0 < r["busy_s"] < r["window_s"]
    # the step program ran, as often as the route program
    step = [v for k, v in r["programs"].items() if "_assemble_and_step" in k]
    route = [v for k, v in r["programs"].items() if "_route_step" in k]
    assert len(step) == 1 and step[0][0] >= 1
    assert step[0][0] == route[0][0]
    # busy is the union of the operations: no more than the programs' sum
    assert r["busy_s"] <= sum(v[1] for v in r["programs"].values()) * 1.001
    assert all("(" not in k for k in r["programs"])


def test_breakdown_lists(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 1 <= len(ops) <= 10 and 1 <= len(gaps) <= 10
    assert ops == sorted(ops, key=lambda x: -x[1])
    idle = sum(s for name, s in gaps if name.startswith("all:"))
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)
    # the host's annotations are found and cover some of the idle time
    assert any(name in ("all:" + lab) for name, _s in gaps for lab in LABELS)


def test_roofline_reader_on_the_recorded_trace(reduced):
    man = Manifest()
    cfg = man.config("base-1k3")
    m = {"name": "step_roofline", "unit": "%", "reader":
         "harness.readers.trace_module_roofline", "module": "_assemble_and_step",
         "bytes_fn": "harness.costs.colocated_step_bytes", "peak": "hbm_bytes_per_s"}
    ctx = {"trace": reduced, "config": cfg, "device_kind": "TPU v5 lite"}
    got = readers.read_all([m], ctx)["step_roofline"]["value"]
    assert 0.5 < got < 20.0
    # a program that is not in the trace: silent, never 0
    assert readers.read_all([{**m, "module": "no_such_program"}], ctx) == {}
    assert readers.read_all([m], {**ctx, "trace": None}) == {}
    with pytest.raises(KeyError):
        readers.read_all([m], {**ctx, "device_kind": "TPU v99"})
