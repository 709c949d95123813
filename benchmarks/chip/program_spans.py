"""The program's own host spans (``loom.*``) in a ``--trace 1`` run.

The serving engine writes one span per phase of a step
(``loom.engine.step``, ``.admit``, ``.prefill``, ``.scatter``,
``.decode``, ``.sync``, ``.emit``) through
``jax.profiler.TraceAnnotation``, the mechanism the benchmark's own
``bench.*`` spans use, so they land on the host plane of the same trace,
on the device trace's clock, each with its keyword stats
(``request_id``, ``slot``, ...). ``trace_reduce`` keeps only the
``bench.*`` spans; this module reads the ``loom.*`` ones from the
``.xplane.pb`` that ``run_cell.py`` leaves in ``<checkout>/.bench_trace``
until it has printed the result line. A later benchmark PR may fold it
into ``trace_reduce``.

A trace without such spans (a program that writes none) gives no
spans, and the metrics that read them return None.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import trace_reduce

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")
PREFIX = "loom."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float            # ns
    end: float              # ns
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def spans_of(pd) -> list:
    """Every ``loom.*`` event of the host plane of a
    ``jax.profiler.ProfileData``, sorted by start."""
    out = []
    for plane in pd.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for ln in plane.lines:
            out += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats))
                    for e in ln.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: (s.start, -s.end))


@functools.lru_cache(maxsize=None)
def load(path: str) -> tuple:
    """The ``loom.*`` spans of one ``.xplane.pb``, parsed once."""
    from jax.profiler import ProfileData
    return tuple(spans_of(ProfileData.from_file(path)))


def in_window(ctx, name: str | None = None) -> list:
    """The spans (of ``name``, else all) that start inside the reduced
    trace's window ``[w0, w1)``; none without a trace or its file."""
    tr = ctx["trace"]
    if tr is None:
        return []
    try:
        path = trace_reduce.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return []
    return [s for s in load(path)
            if tr.w0 <= s.start < tr.w1 and (name is None or s.name == name)]


def mean_ms(spans: list) -> float | None:
    """Mean duration in ms; None for no spans."""
    return sum(s.dur for s in spans) / len(spans) / 1e6 if spans else None
