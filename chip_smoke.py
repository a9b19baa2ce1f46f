"""Run both device paths once on one TPU chip, and check what they return.

    python chip_smoke.py [--seed 0]

Phases, in order; any failure exits non-zero and prints no result line.

1. Device: JAX must see a TPU (``JAX_PLATFORMS=cpu`` fails here).
2. Function layer: the Table I ``asset_damage`` pipeline at its
   published size (ResNet-50, ``width=1.0``, 224x224x3 images) served by
   ``DSCSExecutor`` on the DSCS platform: f1 on the vector-engine kernel,
   f2 as im2col convolutions through the systolic kernel.  Each request
   is compared with the same model's XLA path (``lax.conv_general_dilated``,
   the executor on a CPU platform), with matmul precision pinned to
   float32 on both sides.
3. Simulator: the ``poisson-10m-f1024`` fleet of
   ``benchmarks/bench_engine.py`` (~10^7 Poisson requests, 1024 drives,
   1024 CPU nodes, 0.95 utilization, 0.08 s hedge, 8 shards, one
   process) through ``ClusterSim.run_sharded`` with the device Lindley
   solve (``backend="pallas"``) and with the float64 host solve
   (``backend="segmented"``), compared under the kernel's error bound.

Times printed on the way are bring-up observations, not benchmark
numbers.  The last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# Logits tolerance, relative to the largest |logit| of the XLA path.  Both
# sides compute in float32 but sum the same products in different orders
# (the kernel accumulates 128-wide K tiles of the im2col matrix, XLA
# reduces the convolution its own way), so every layer differs by a few
# float32 roundings of its activations; the per-sample batch norm after
# each convolution rescales rather than grows them over the 53 layers.
# 1e-3 leaves two orders of magnitude over that.
LOGIT_RTOL = 1e-3
# Simulated statistics: a hedge decision flips only where a drive-queue
# wait lies within the float32 bound of the hedge budget; each flip moves
# one CPU copy and the CPU queue behind it on one node.  A few flips
# among 10^7 requests move latency percentiles and winner shares far
# less than these.
STAT_RTOL = 1e-3
SHARE_ATOL = 1e-4
N_REQUESTS = 8          # function-layer requests, after one warm-up


def device_phase() -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {devs[0].platform!r}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: {dev['kind']} x{dev['count']}", flush=True)
    return dev


def function_phase(seed: int, n_requests: int, image_size: int = 224,
                   width: float = 1.0) -> dict:
    """Serve ``n_requests`` through the kernel path and the XLA path;
    returns the worst logits error, the top-1 agreement and the number of
    Pallas kernels in the lowered program."""
    import jax
    import numpy as np
    from repro.core.executor import DSCSExecutor

    with jax.default_matmul_precision("float32"):
        dsa = DSCSExecutor("asset_damage", platform="DSCS-Serverless",
                           image_size=image_size, width=width, seed=seed)
        xla = DSCSExecutor("asset_damage", platform="Baseline-CPU",
                           image_size=image_size, width=width, seed=seed)
        keys = jax.random.split(jax.random.PRNGKey(seed), n_requests)
        reqs = [dsa.make_request(k) for k in keys]
        kernels = dsa.lower(reqs[0]).as_text().count("tpu_custom_call")
        for name, ex in (("kernel", dsa), ("xla", xla)):
            t0 = time.perf_counter()
            jax.block_until_ready(ex(reqs[0]).output)
            print(f"f1+f2 {name} path: first call (compile included) "
                  f"{time.perf_counter() - t0:.3f} s", flush=True)
        worst, agree, walls = 0.0, 0, []
        for req in reqs:
            t0 = time.perf_counter()
            got = dsa(req)
            jax.block_until_ready(got.output)
            walls.append(time.perf_counter() - t0)
            want = np.asarray(xla(req).output)
            out = np.asarray(got.output)
            if not np.all(np.isfinite(out)) or out.shape != (1, 1000):
                raise RuntimeError(f"bad logits: shape {out.shape}")
            worst = max(worst, float(np.max(np.abs(out - want))
                                     / np.max(np.abs(want))))
            agree += int(np.array_equal(got.result, want.argmax(-1)))
    print(f"f1+f2 kernel path: per-request wall after warm-up "
          f"{[round(w, 4) for w in walls]} s", flush=True)
    print(f"logits vs XLA path: max |diff| / max |logit| = {worst:.3e} "
          f"(tolerance {LOGIT_RTOL}), top-1 equal on {agree}/{n_requests}, "
          f"{kernels} Pallas kernels in the lowered program", flush=True)
    return {"worst": worst, "agree": agree, "kernels": kernels}


def _stats(tr) -> dict:
    import numpy as np
    lat = tr.finish - tr.arrival
    return {"mean": float(np.mean(lat)),
            "p50": float(np.percentile(lat, 50)),
            "p99": float(np.percentile(lat, 99)),
            "dscs_share": float(np.mean(tr.winner == 0)),
            "cpu_share": float(np.mean(tr.winner == 1))}


def simulator_phase(cfg: dict, seed: int) -> dict:
    """Run ``cfg`` with the device and the host Lindley solve and compare
    them: per-request drive-queue starts against the kernel's error bound,
    hedge flips, and the headline statistics."""
    import numpy as np
    from benchmarks.bench_engine import BENCH_SHARDS, workload
    from repro.core.engine import _placement
    from repro.core.lindley import fcfs_queues, segment_error_bound
    from repro.core.scheduler import ClusterSim

    pipes, arrivals, duration = workload(cfg)
    traces = {}
    for backend in ("pallas", "segmented"):
        sim = ClusterSim(n_dscs=cfg["n_dscs"], n_cpu=cfg["n_cpu"],
                         hedge_budget_s=cfg["hedge_budget_s"], seed=seed)
        t0 = time.perf_counter()
        traces[backend] = sim.run_sharded(
            pipes, arrivals=arrivals, duration_s=duration,
            n_shards=BENCH_SHARDS, processes=1, backend=backend)
        print(f"simulator {backend}: {traces[backend].n} requests in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
    dev, host = traces["pallas"], traces["segmented"]
    if not np.array_equal(np.isnan(dev.dscs_finish),
                          np.isnan(host.dscs_finish)):
        raise RuntimeError("the backends sent different requests to drives")
    rids, seg, t, s, start = fcfs_queues(
        _placement(cfg["n_dscs"], host.n), host.arrival, host.dscs_finish,
        cfg["n_dscs"])
    bound = segment_error_bound(seg, t, s)
    diff = np.abs(dev.dscs_finish[rids] - host.dscs_finish[rids])
    flipped = (dev.hedged != host.hedged)[rids]
    near = np.abs(start - t - cfg["hedge_budget_s"]) <= bound
    a, b = _stats(dev), _stats(host)
    print(f"drive-queue start: max |pallas - segmented| = {diff.max():.3e} s "
          f"(bound there {bound[diff.argmax()]:.3e} s, largest bound "
          f"{bound.max():.3e} s); {int(np.sum(diff > bound))} requests "
          f"outside their bound", flush=True)
    print(f"hedge outcome flipped on {int(flipped.sum())} requests, "
          f"{int(np.sum(flipped & ~near))} of them outside the bound",
          flush=True)
    print("stats pallas    " + json.dumps(a), flush=True)
    print("stats segmented " + json.dumps(b), flush=True)
    if np.any(diff > bound) or np.any(flipped & ~near):
        raise RuntimeError("device Lindley solve outside its error bound")
    for k in ("mean", "p50", "p99"):
        if abs(a[k] - b[k]) > STAT_RTOL * abs(b[k]):
            raise RuntimeError(f"latency {k}: {a[k]} vs {b[k]}")
    for k in ("dscs_share", "cpu_share"):
        if abs(a[k] - b[k]) > SHARE_ATOL:
            raise RuntimeError(f"winner {k}: {a[k]} vs {b[k]}")
    return {"max_diff": float(diff.max()), "flips": int(flipped.sum())}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, requests and arrivals")
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent
    sys.path[:0] = [str(root / "src"), str(root)]

    dev = device_phase()
    from benchmarks.bench_engine import CONFIGS
    from repro.jax_cache import use_compile_cache
    use_compile_cache()

    fn = function_phase(args.seed, N_REQUESTS)
    if not fn["kernels"]:
        raise RuntimeError("no tpu_custom_call in the kernel path: the "
                           "Pallas kernels did not compile")
    if fn["worst"] > LOGIT_RTOL or fn["agree"] != N_REQUESTS:
        raise RuntimeError("kernel path disagrees with the XLA path")
    cfg = next(c for c in CONFIGS if c["name"] == "poisson-10m-f1024")
    simulator_phase(cfg, args.seed)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
