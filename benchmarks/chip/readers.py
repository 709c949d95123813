"""What the metric readers under ``metrics/`` share.

``ctx`` holds ``rec``, the driver's record of the run (host-clock
stamps and counts), ``peaks``, the chip's peaks from ``peaks.json``, and
``trace``, the reduced profiler trace of a ``--trace 1`` run (else None).
A reader that finds nothing to read returns None, and the metric is left
out of the result.
"""
from __future__ import annotations

import numpy as np

import work


def pct(values, q: float) -> float | None:
    """The ``q``-th percentile (numpy's linear interpolation); None for
    no values, and None where the percentile is infinite."""
    if len(values) == 0:
        return None
    v = float(np.percentile(np.asarray(values, np.float64), q))
    return v if np.isfinite(v) else None


def lm(ctx) -> dict | None:
    rec = ctx["rec"]
    return rec if rec.get("kind") == "lm" else None


def cnn(ctx) -> dict | None:
    rec = ctx["rec"]
    return rec if rec.get("kind") == "cnn" else None


def window_requests(rec) -> list:
    return [r for r in rec["reqs"] if r.arrival.phase == "window"]


def window_steps(rec) -> list:
    return [s for s in rec["steps"] if rec["t_w0"] <= s.t0 < rec["t_w1"]]


def pct_of(part: float, whole: float) -> float | None:
    return 100.0 * part / whole if whole > 0 else None


def decode_least_s(rec, pk, prefix: str = "") -> float | None:
    """Least seconds of every decode linear whose name starts with
    ``prefix`` over the window's steps, each at its own bound, with the
    step's active rows; None where the configuration's reference states
    no decode work."""
    c = rec["config"]
    ref = work.lm_decode(c)
    if ref is None:
        return None
    linears = [(k, n, count) for name, k, n, count in ref.decode_linears(c)
               if name.startswith(prefix)]
    total = 0.0
    for s in window_steps(rec):
        if s.contexts:
            for k, n, count in linears:
                total += count * work.least_time(
                    *work.linear(len(s.contexts), k, n), pk)
    return total
