"""Readings that the limit of a cell's ``worst_gap`` is set from.

    python3 -m bench.calibrate --workload <name> --seeds 1,2,3 --seconds 2

For each seed, in one process: one run of the cell as ``bench.run``
makes it (the served path over a short window at the cell's own load,
its sampled answers compared with the reference), and the control on
the same sampled frames: the reference in three bfloat16 passes,
answering with its own top-1.  Prints one JSON line per seed; the lower
reading of the limit is the largest ``worst_gap``, the upper the
smallest ``control_gap``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(run.ROOT / "src"), str(run.ROOT)]
    cell = run.load_cell(run.ROOT, args.workload)
    import jax
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, control=True,
                           t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "worst_gap": res["checks"]["worst_gap"]["value"],
                          "control_gap": res["control_gap"],
                          "attempted": res["attempted"],
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
