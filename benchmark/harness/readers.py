"""Readers: how a metric's file turns what a run recorded into a number.

A metric's file names one reader by its dotted path
(``harness.readers.counter_ratio``; a later PR's reader is a new module)
and gives its arguments.  A reader gets the run's context and returns a number, or None where it found nothing to
read (the harness then leaves the metric out of the line).

  ctx["table"]    flat window deltas: engine.*, gateway.*, loadgen.*
  ctx["series"]   latency samples of the window, ms, by name
  ctx["missing"]  per series, operations that never got an answer
  ctx["trace"]    xplane.reduce_xplane(...) of the traced slice, or None
  ctx["config"]   the configuration's file
  ctx["device_kind"], ctx["setup_s"], ctx["op_timeout_ms"]
"""
from __future__ import annotations

from . import costs, traffic
from .manifest import resolve


def _total(table: dict, keys) -> float | None:
    if any(k not in table for k in keys):
        return None
    return float(sum(table[k] for k in keys))


def counter_ratio(m: dict, ctx: dict):
    """scale * sum(num) / sum(den), or scale * sum(num) with no ``den``;
    nothing where a key is absent or the denominator is 0."""
    num = _total(ctx["table"], m["num"])
    den = _total(ctx["table"], m["den"]) if "den" in m else 1.0
    if num is None or not den:
        return None
    return m.get("scale", 1.0) * num / den


def loadgen_percentile(m: dict, ctx: dict):
    """Percentile ``q`` of a latency series; an operation that got no
    answer sits above every other and reads as the operation's timeout."""
    values = ctx["series"].get(m["series"], [])
    n_missing = ctx["missing"].get(m["series"], 0)
    return traffic.percentile(values, m["q"], n_missing,
                              missing=ctx["op_timeout_ms"])


def trace_module_roofline(m: dict, ctx: dict):
    """Least time of one run of a device program (its bytes over the
    chip's peak) over the mean device time of its runs in the trace."""
    trace = ctx.get("trace")
    if not trace:
        return None
    runs = [v for k, v in trace["programs"].items() if m["module"] in k]
    n = sum(r[0] for r in runs)
    if not n:
        return None
    mean_s = sum(r[1] for r in runs) / n
    need = resolve(m["bytes_fn"])(**ctx["config"]["engine"])
    peak = costs.peaks(ctx["device_kind"])[m["peak"]]
    return 100.0 * (need / peak) / mean_s


def setup(m: dict, ctx: dict):
    return ctx["setup_s"]


def read_all(metrics: list, ctx: dict) -> dict:
    out = {}
    for m in metrics:
        value = resolve(m["reader"])(m, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
