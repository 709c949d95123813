"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is looked up in
``BENCHMARK.json``; its configuration, traffic mix, driver and metrics
are files under this directory, found by name (``manifest.py``).

Without a TPU, or with fewer chips than the cell asks for, the command
exits non-zero and prints no result. Otherwise it runs the cell once in
this process and prints, as its last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared for ``correct`` with its limit.
The same checks close standard error, one per line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# Programs that may wait on a chip at once. The runtime's default, 32,
# cuts a driver that keeps seconds of calls in flight to about one.
MAX_INFLIGHT = 256


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _setup_paths() -> None:
    # libtpu logs under /tmp unless told otherwise; a run writes only
    # inside its checkout and the directories it is given.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (HERE, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout; every program is cached, however quickly it compiled."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_chips(need: int):
    """The TPU devices, or None when this machine has too few."""
    import jax
    jax.config.update("jax_pjrt_client_create_options",
                      {"max_inflight_computations": MAX_INFLIGHT})
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu" or len(devs) < need:
        log(f"needs {need} TPU chip(s); JAX found "
            f"{[d.platform for d in devs]}: refusing to report host numbers")
        return None
    return devs


def result_line(cell, rec: dict, devs, trace: bool, peaks: dict) -> dict:
    """The last line: the metrics from their readers, the device, with a
    trace its breakdown, and last the checks."""
    import manifest
    import trace_reduce
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    ctx = {"rec": rec, "peaks": peaks, "trace": None}
    if trace:
        ctx["trace"] = trace_reduce.reduce_dir(TRACE_DIR, cell.chips)
        device.update(busy_s=ctx["trace"].busy_s,
                      window_s=ctx["trace"].window_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = ctx["trace"].breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in rec["checks"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_paths()
    import manifest
    cell = manifest.cell(args.workload)

    devs = find_chips(cell.chips)
    if devs is None:
        return 3
    enable_compile_cache()
    import work
    peaks = work.peaks(devs[0].device_kind)
    log(f"cell {cell.name}: device {devs[0].device_kind} x{len(devs)}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    driver = manifest.load_module("drivers", cell.config["kind"])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    rec = driver.run(cell, args.seed, args.seconds,
                     TRACE_DIR if args.trace else None,
                     t_start=T_START, log=log)
    log(f"setup_s={rec['setup_s']:.3f} window_s={rec['window_s']:.3f} "
        f"compiles_in_window={rec['compiles_in_window']}")
    line = result_line(cell, rec, devs, bool(args.trace), peaks)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    for k, (v, lim) in rec["checks"].items():
        log(f"check {k} = {v!r} limit {lim!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
