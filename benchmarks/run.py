"""Benchmark harness: one function per paper table/figure, plus the
roofline summary.  Prints ``name,us_per_call,derived`` CSV (for analytic
figures the middle column is the metric value),
or a ``figures/v2`` JSON envelope ``{schema, seed, smoke, rows}`` with
``--json`` — each row is ``{name, value, derived, ci95}`` where ``ci95``
is null for a single run and a ``[mean, halfwidth]`` pair when emitted by
``benchmarks.montecarlo``.

    python -m benchmarks.run                  # everything
    python -m benchmarks.run --only fig19     # one figure family
    python -m benchmarks.run --list           # enumerate figures
    python -m benchmarks.run --only fig12 --json   # machine-readable rows
    python -m benchmarks.run --only fig21 --smoke --json  # CI fast path
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _roofline_summary():
    """Condense the dry-run JSONs into headline roofline rows."""
    import glob
    import json
    rows = []
    files = sorted(glob.glob("results/dryrun/*__single__train.json"))
    for f in files:
        r = json.load(open(f))
        if r.get("status") != "ok":
            continue
        t = r["roofline"]
        bound = max(t["compute_s"], t["memory_s"], t["collective_s"])
        rows.append((f"roofline/{r['arch']}/{r['shape']}", bound,
                     f"dom={t['dominant']} frac={t['roofline_fraction']:.3f}"))
    return rows


def main(argv=None) -> None:
    from benchmarks import figures as figures_mod
    from benchmarks.figures import ALL_FIGURES
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="run only figures whose name contains this")
    ap.add_argument("--list", action="store_true", dest="list_figs",
                    help="print figure names and exit")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit a JSON array of rows instead of CSV")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink expensive simulation figures to the "
                         "CI-sized fast path (same structure and "
                         "acceptance ratios)")
    ap.add_argument("--seed", type=int, default=0,
                    help="simulation seed for every figure (montecarlo "
                         "fans one config across many seeds)")
    args = ap.parse_args(argv)
    if args.smoke:
        figures_mod.SMOKE = True
    figures_mod.SEED = args.seed
    figures = [f for f in ALL_FIGURES
               if args.only.lower() in f.__name__.lower()]
    if args.list_figs:
        for fig in figures:
            print(fig.__name__)
        return

    collected = []

    def emit(name, val, derived):
        if args.as_json:
            collected.append({"name": name, "value": float(val),
                              "derived": str(derived), "ci95": None})
        else:
            print(f"{name},{val:.6g},{derived}")
            sys.stdout.flush()

    if not args.as_json:
        print("name,us_per_call,derived")
    failures = []
    for fig in figures:
        t0 = time.perf_counter()
        try:
            rows = fig()
        except Exception as exc:        # noqa: BLE001 - report, then fail run
            failures.append(fig.__name__)
            print(f"FAILED {fig.__name__}: {type(exc).__name__}: {exc}",
                  file=sys.stderr)
            continue
        dt = (time.perf_counter() - t0) * 1e6
        for name, val, derived in rows:
            emit(name, val, derived)
        emit(f"{fig.__name__}/wall", dt, "us")
    if not args.only:
        for name, val, derived in _roofline_summary():
            emit(name, val, derived)
    if args.as_json:
        # figures/v2 envelope: single-run rows carry ci95=null; the
        # montecarlo driver replaces them with [mean, halfwidth] pairs
        json.dump({"schema": "figures/v2", "seed": args.seed,
                   "smoke": bool(args.smoke), "rows": collected},
                  sys.stdout, indent=2)
        print()
    if failures:
        # exit non-zero so CI smoke gates never read a partial sweep as
        # a pass; the JSON above is still complete for what did run
        raise SystemExit(f"{len(failures)} figure(s) failed: "
                         + ", ".join(failures))


if __name__ == "__main__":
    main()
