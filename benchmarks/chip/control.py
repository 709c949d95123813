"""The readings that set each limit of ``correct``: the program's and
its control's, on several seeds, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds <s>]

Each seed is one whole run of the cell (set-up, pre-roll, window,
reference), and after it the configuration's ``control`` - the
reference computed at the lower precision it names - is put in the
program's place on the same sample and judged by the same checks
(``checks.py``). One JSON line per seed gives the program's and the
control's checks and ``correct``: the control's has to be false. The
benchmark's own runs never run the control. Needs a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import checks
import run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    run_cell._setup_paths()
    import manifest
    cell = manifest.cell(args.workload)
    if run_cell.find_chips(cell.chips) is None:
        return 3
    run_cell.enable_compile_cache()
    seconds = args.seconds or manifest.load_manifest()["run_seconds"]
    driver = manifest.load_module("drivers", cell.config["kind"])
    ctl = cell.config["control"]
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = driver.run(cell, seed, seconds, None,
                         t_start=time.perf_counter(), log=run_cell.log,
                         control=(ctl["a_bits"], ctl["w_bits"]))
        ctl_checks = rec["control_checks"]
        print(json.dumps({
            "seed": seed,
            "program": {"correct": rec["correct"], "checks": rec["checks"]},
            "control": {"correct": checks.correct(ctl_checks),
                        "checks": ctl_checks}}), flush=True)
        del rec
        gc.collect()    # free this seed's state before the next is built
    return 0


if __name__ == "__main__":
    sys.exit(main())
