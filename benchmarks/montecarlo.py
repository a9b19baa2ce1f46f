"""Many-seed Monte Carlo driver for the figure benchmarks.

Fans one figure configuration across ``--seeds`` independent simulation
seeds (one ``python -m benchmarks.run --json --seed s`` subprocess per
seed, optionally ``--jobs`` of them at once) and aggregates every
headline metric into ``mean ± 95% CI``.  Output is the same
``figures/v2`` envelope ``benchmarks.run --json`` emits, with each row's
``ci95`` field filled in as a ``[mean, halfwidth]`` pair — so anything
that can read a single-seed sweep can read a Monte Carlo sweep.

    python -m benchmarks.montecarlo --only fig19 --seeds 8
    python -m benchmarks.montecarlo --smoke --seeds 8 --json mc.json

Per-run bookkeeping rows (``*/wall`` timings) are dropped: wall time
varies with host load, not with the seed, and a CI on it would be
noise dressed up as signal.  Metrics that go non-finite on any seed
(e.g. an all-abandoned run pushing a percentile to ``inf``) keep
``value`` from the first seed and report ``ci95: null`` rather than a
meaningless interval.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ci95(values: Sequence[float]) -> Tuple[float, Optional[float]]:
    """Mean and normal-approximation 95% half-width of ``values``.

    >>> mean, half = ci95([2.0, 4.0, 4.0, 4.0, 5.0, 5.0, 7.0, 9.0])
    >>> round(mean, 3), round(half, 3)
    (5.0, 1.482)
    >>> ci95([3.5])
    (3.5, None)
    """
    vals = [float(v) for v in values]
    n = len(vals)
    mean = sum(vals) / n
    if n < 2:
        return mean, None
    var = sum((v - mean) ** 2 for v in vals) / (n - 1)
    return mean, 1.96 * math.sqrt(var / n)


def seed_command(seed: int, only: str, smoke: bool) -> List[str]:
    """The ``benchmarks.run`` command line for one seed.  It always
    filters to figures (every figure is named ``fig*``): the roofline
    rows that an unfiltered run adds do not depend on the seed."""
    cmd = [sys.executable, "-m", "benchmarks.run", "--json",
           "--seed", str(seed), "--only", only or "fig"]
    if smoke:
        cmd += ["--smoke"]
    return cmd


def _run_one_seed(seed: int, only: str, smoke: bool) -> List[dict]:
    cmd = seed_command(seed, only, smoke)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), REPO,
                    env.get("PYTHONPATH", "")) if p)
    out = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                         text=True)
    if out.returncode != 0:
        raise RuntimeError(f"seed {seed} run failed:\n{out.stderr}")
    return json.loads(out.stdout)["rows"]


def aggregate(per_seed_rows: List[List[dict]]) -> List[dict]:
    """Merge per-seed row lists into one list with ``ci95`` filled in.

    Row order follows the first seed; ``*/wall`` rows are dropped; a
    metric missing from some seed or non-finite on any seed keeps the
    first seed's value with ``ci95: null``.
    """
    series: Dict[str, List[float]] = {}
    for rows in per_seed_rows:
        for r in rows:
            if r["name"].endswith("/wall"):
                continue
            series.setdefault(r["name"], []).append(r["value"])
    out = []
    n_seeds = len(per_seed_rows)
    for r in per_seed_rows[0]:
        name = r["name"]
        if name.endswith("/wall"):
            continue
        vals = series[name]
        finite = all(math.isfinite(v) for v in vals)
        if finite and len(vals) == n_seeds:
            mean, half = ci95(vals)
            out.append({"name": name, "value": mean,
                        "derived": r["derived"],
                        "ci95": None if half is None else [mean, half]})
        else:
            out.append({"name": name, "value": r["value"],
                        "derived": r["derived"], "ci95": None})
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="run only figures whose name contains this")
    ap.add_argument("--seeds", type=int, default=8,
                    help="number of independent seeds (>= 8 for the "
                         "committed figure JSONs)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized fast path for every figure")
    ap.add_argument("--jobs", type=int, default=1,
                    help="seed subprocesses to run concurrently")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the figures/v2 envelope here instead of "
                         "stdout CSV")
    args = ap.parse_args(argv)
    if args.seeds < 1:
        raise SystemExit("--seeds must be >= 1")

    seeds = list(range(args.seeds))
    if args.jobs > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=args.jobs) as pool:
            per_seed = list(pool.map(
                lambda s: _run_one_seed(s, args.only, args.smoke), seeds))
    else:
        per_seed = [_run_one_seed(s, args.only, args.smoke) for s in seeds]

    rows = aggregate(per_seed)
    envelope = {"schema": "figures/v2", "seeds": args.seeds,
                "smoke": bool(args.smoke), "rows": rows}
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(envelope, fh, indent=2)
            fh.write("\n")
        print(f"wrote {args.json} ({len(rows)} rows, "
              f"{args.seeds} seeds)")
    else:
        print("name,mean,ci95_halfwidth,derived")
        for r in rows:
            half = "" if r["ci95"] is None else f"{r['ci95'][1]:.6g}"
            print(f"{r['name']},{r['value']:.6g},{half},{r['derived']}")


if __name__ == "__main__":
    main()
