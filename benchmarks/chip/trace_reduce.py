"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The trace holds, on one clock in nanoseconds:

- one plane per chip, ``/device:TPU:<i>``, with the line ``XLA Modules``
  (one event per run of a compiled program, named ``jit_<fn>(<id>)``)
  and the line ``XLA Ops`` (one event per operation; the name is the HLO
  instruction, ``%<op>.<n> = <shape> <opcode>(...)``; a Pallas kernel is
  a ``custom-call`` named after its kernel, such as ``bitserial_matmul``);
- the host plane ``/host:CPU``, whose threads carry the benchmark's own
  spans (``jax.profiler.TraceAnnotation``, named ``bench.<what>``).

The window is the host span ``bench.window``. Busy time is the union of
the operations' intervals inside it (async copies excluded), averaged
over the chips; an idle gap is a stretch of the window in which no
operation ran, split at the edges of the ``bench.*`` host spans and
labelled by the innermost span the host was in.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(r"^%?([^ ]+)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns
    end: float              # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


def op_name(hlo: str) -> str:
    """``%bitserial_conv.21 = s32[...] custom-call(...)`` -> the
    instruction's name, ``bitserial_conv.21``."""
    m = _OP_NAME.match(hlo)
    return m.group(1) if m else hlo


def program_name(module: str) -> str:
    """``jit_decode_fn(1234)`` -> ``jit_decode_fn``."""
    return module.split("(")[0]


def _merge(events) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((ev.start, ev.end) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Intervals:
    """Disjoint sorted intervals with overlap queries."""

    def __init__(self, events):
        self.iv = _merge(events)
        self.starts = [s for s, _ in self.iv]

    def overlap(self, a: float, b: float) -> float:
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        tot = 0.0
        while i < len(self.iv) and self.iv[i][0] < b:
            s, e = self.iv[i]
            tot += max(0.0, min(e, b) - max(s, a))
            i += 1
        return tot

    def gaps(self, a: float, b: float) -> list[tuple[float, float]]:
        out, t = [], a
        for s, e in self.iv:
            if e <= a or s >= b:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < b:
            out.append((t, b))
        return out


def _self_times(events) -> list[tuple[Event, float]]:
    """Each event with its duration less that of the events it holds."""
    out: list[list] = []
    stack: list[list] = []
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            stack.pop()
        if stack and ev.end <= stack[-1][0].end:
            stack[-1][1] -= ev.dur
        item = [ev, ev.dur]
        out.append(item)
        stack.append(item)
    return [(ev, own) for ev, own in out]


@dataclasses.dataclass
class Reduced:
    w0: float
    w1: float
    modules: list            # per chip: [Event] of programs in the window
    ops: list                # per chip: [Event] of operations in the window
    spans: list              # [Event] of bench.* host spans

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) / 1e9

    @property
    def busy(self) -> list:
        if not hasattr(self, "_busy"):
            self._busy = [Intervals(ops) for ops in self.ops]
        return self._busy

    @property
    def busy_s(self) -> float:
        vals = [b.overlap(self.w0, self.w1) for b in self.busy]
        return sum(vals) / len(vals) / 1e9 if vals else 0.0

    def programs(self, pattern: str, chip: int = 0) -> list:
        """Program runs on ``chip`` whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return [m for m in self.modules[chip]
                if rx.search(program_name(m.name))]

    def ops_in(self, runs: list, pattern: str, chip: int = 0) -> list:
        """Operations on ``chip`` inside any of ``runs`` whose
        instruction name matches ``pattern``."""
        rx = re.compile(pattern)
        runs = sorted(runs, key=lambda r: r.start)
        starts = [r.start for r in runs]
        out = []
        for op in self.ops[chip]:
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.start < runs[i].end and rx.search(op_name(op.name)):
                out.append(op)
        return out

    def host_spans(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def device_busy_in(self, a: float, b: float) -> float:
        """Seconds of device activity inside [a, b], averaged over chips."""
        vals = [bb.overlap(a, b) for bb in self.busy]
        return sum(vals) / len(vals) / 1e9 if vals else 0.0

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds of chip 0 in the window, by the innermost host
        span the host was in (a gap that spans several is split)."""
        inner = sorted((s for s in self.spans if s.name != WINDOW_SPAN),
                       key=lambda s: s.start)
        starts = [s.start for s in inner]
        cuts = sorted({t for s in inner for t in (s.start, s.end)})
        out: dict[str, float] = {}
        for a, b in self.busy[0].gaps(self.w0, self.w1):
            lo_i, hi_i = bisect.bisect_right(cuts, a), bisect.bisect_left(
                cuts, b)
            edges = [a] + cuts[lo_i:hi_i] + [b]
            for lo, hi in zip(edges, edges[1:]):
                mid, label = (lo + hi) / 2, "outside any bench span"
                # the latest-starting span that holds the midpoint
                i = bisect.bisect_right(starts, mid) - 1
                for s in inner[max(0, i - 8):i + 1][::-1]:
                    if s.end >= mid:
                        label = s.name
                        break
                out[label] = out.get(label, 0.0) + (hi - lo) / 1e9
        return out

    def breakdown(self) -> dict:
        """Top device operations by self time (an operation that holds
        others, such as the ``while`` of a scan over layers, less the
        operations inside it), and idle time by host span."""
        per: dict[str, float] = {}
        runs = sorted(self.modules[0], key=lambda m: m.start)
        starts = [r.start for r in runs]
        for op, own in _self_times(self.ops[0]):
            i = bisect.bisect_right(starts, op.start) - 1
            prog = program_name(runs[i].name) if i >= 0 else "?"
            key = f"{prog}/{op_name(op.name)}"
            per[key] = per.get(key, 0.0) + own / 1e9
        top = sorted(per.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def span(name: str, on: bool):
    """A host span ``name`` in the profiler's trace, while it records."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def count_compiles() -> list:
    """A list that grows by one name for each program JAX lowers from now
    on: a program compiled in the measured window shows here."""
    from jax import monitoring
    seen: list = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: seen.append(name)
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)
    return seen


def start(trace_dir: str) -> None:
    """Start the profiler: device and host events, the host's own spans;
    no Python function tracing and no HLO dump, which would swamp it."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_file(path: str, n_devices: int = 1) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), n_devices)


def reduce_profile(pd, n_devices: int = 1) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to the window's events."""
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(plane.name[len(DEVICE_PREFIX):])] = tuple(
                [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in lines[key].events] if key in lines else []
                for key in ("XLA Modules", "XLA Ops"))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                spans += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in ln.events if e.name.startswith(SPAN_PREFIX)]
    if len(devices) < n_devices:
        raise ValueError(f"trace holds {len(devices)} TPU planes, the cell "
                         f"uses {n_devices}")
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    w0, w1 = win[0].start, win[0].end
    inside = lambda evs: [e for e in evs if e.start >= w0 and e.start < w1]
    chips = sorted(devices)[:n_devices]
    return Reduced(w0=w0, w1=w1,
                   modules=[inside(devices[c][0]) for c in chips],
                   ops=[inside(devices[c][1]) for c in chips],
                   spans=[s for s in spans if s.end > w0 and s.start < w1])


def reduce_dir(trace_dir: str, n_devices: int = 1) -> Reduced:
    return reduce_file(find_xplane(trace_dir), n_devices)
