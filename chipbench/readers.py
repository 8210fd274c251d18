"""Helpers the metric readers share: percentiles, window slices, deltas.

A reader gets the run's collected data as one dict:

  config, traffic     the cell's configuration and traffic mix
  seconds, t0, t1     the window (host ``perf_counter`` seconds)
  t_start, t_stop     process start; when the harness stopped waiting
  records             one dict per request (loadgen.Req.as_record)
  snap0, snap1        ``/v1/stats`` and parsed ``/metrics`` at t0 and t1
  trace               the reduced device trace (``--trace 1``), else None
  peaks               the chip's row of roofline/peaks.json
"""
from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile, interpolated between order statistics (Python's
    ``statistics.quantiles`` with the inclusive method)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of nothing")
    if len(vals) == 1:
        return float(vals[0])
    return float(statistics.quantiles(vals, n=100, method="inclusive")[
        int(round(q)) - 1])


def stat_delta(run: dict, key: str) -> float:
    return float(run["snap1"]["stats"][key]) - float(run["snap0"]["stats"][key])


def metric_delta(run: dict, key: str) -> float:
    return (run["snap1"]["metrics"].get(key, 0.0)
            - run["snap0"]["metrics"].get(key, 0.0))


def window_records(run: dict) -> list[dict]:
    """Requests the window offered: not warm-up traffic."""
    return [r for r in run["records"] if not r["warmup"]]


def tokens_in_window(rec: dict, t0: float, t1: float) -> list[float]:
    return [t for t in rec["times"] if t0 <= t <= t1]
