"""Find an LM cell's knee: the highest arrival rate with no growing backlog.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 0.5,1,1.5 \\
        [--seconds 40] [--seed 1]

One process, one set-up: the cell's configuration and engine are built
once, then each rate runs the cell's traffic (with ``rate_rps`` replaced)
through a fresh engine for a pre-roll and ``--seconds`` of window. For
each rate it prints one JSON line: offered and completed requests per
second, output tokens per second, TTFT p50/p95 of the requests due in
the window, the queue depth at the window's middle and end, and
``growing``: whether the backlog grew through the window (the queue at
the end holds more than at the middle, and at least a tenth of the
window's requests). The last line gives the knee, the highest rate below
the first that grows, and 0.8 of it, which the cell's traffic file then
offers. Needs a TPU, like ``run_cell.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import numpy as np

import run_cell


def sweep_rate(lm, cell, sess, rate: float, seed: int, seconds: float):
    from repro.runtime.batching import BatchingEngine
    mix = dict(cell.traffic, rate_rps=rate)
    c2 = dataclasses.replace(cell, traffic=mix)
    eng = BatchingEngine(sess, max_batch=mix["max_batch"],
                         max_seq=mix["max_seq"])
    t_win = time.perf_counter() + float(mix["preroll_s"])
    reqs = lm.requests(c2, seed, seconds, t_win)
    loop = lm.OpenLoop(eng, reqs)
    loop.drive(t_win)
    loop.drive(t_win + seconds / 2)
    depth_mid = eng.scheduler.depth
    loop.drive(t_win + seconds)
    depth_end = eng.scheduler.depth
    t_end = time.perf_counter()
    window = [r for r in reqs if r.arrival.phase == "window"]
    ttft = [(r.times[0] - r.due) * 1e3 for r in window if r.times]
    toks = sum(1 for r in reqs for t in r.times if t_win <= t < t_end)
    done = sum(1 for r in window if r.handle is not None and r.handle.done)
    loop.eng = None
    del eng
    return {
        "rate_rps": rate, "window_s": t_end - t_win,
        "arrived": len(window), "done": done,
        "done_per_s": done / (t_end - t_win),
        "tokens_per_s": toks / (t_end - t_win),
        "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
        "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
        "no_first_token": len(window) - len(ttft),
        "queue_mid": depth_mid, "queue_end": depth_end,
        "growing": bool(depth_end > depth_mid
                        and depth_end >= 0.1 * len(window)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run_cell._setup_paths()
    import manifest
    cell = manifest.cell(args.workload)
    if run_cell.find_chips(cell.chips) is None:
        return 3
    run_cell.enable_compile_cache()
    lm = manifest.load_module("drivers", "lm")
    sess, eng = lm.build(cell, args.seed, run_cell.log)
    del eng
    gc.collect()
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        row = sweep_rate(lm, cell, sess, rate, args.seed, args.seconds)
        gc.collect()    # free this rate's KV pool before the next is built
        print(json.dumps(row), flush=True)
        if row["growing"]:
            break
        knee = rate
    print(json.dumps({"knee_rps": knee,
                      "offer_rps": None if knee is None else 0.8 * knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
