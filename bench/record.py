"""Serve one traced window of a cell, as ``bench.run --trace 1`` does, and
keep what that run throws away: the trace and the compiled f1+f2
program's text.  Prints where the window's time went by named scope and by
host span.

    python3 -m bench.record --workload <name> --seed <n> --seconds <s> \\
        --out <dir> [--width <w>] [--image-size <n>]

From the root of a checkout.  Writes ``<dir>/<workload>.xplane.pb.gz`` and
``<dir>/<workload>.hlo.txt.gz``; ``--width`` and ``--image-size`` override
the configuration's (a trace small enough to keep with the tests).  The
last line of standard output is one JSON object, times in ms per request:
``device`` by scope (``f1``, each ``conv<i>``'s ``im2col``, ``weights``
and ``gemm``, ``f2`` outside the convolutions, ``unscoped``, ``other``
programs), ``host`` by span (``pick``, ``invoke``, ``f1f2``, ``f3``,
``account``, ``wait``, ``fetch``, ``dispatch_host_ms``,
``account_host_ms``), ``idle`` by the innermost host span over each idle
gap of the device, and ``borrowed``: the ops that carry no scope of their
own and the neighbour's scope each took.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
TOP = 12


def report(t, text: str, host: list, requests: int) -> dict:
    """The window of the loaded trace ``t`` by scope and by host span;
    ``host`` holds the program's spans (:func:`bench.scopes.host_spans`)."""
    from bench import scopes
    smap, own = scopes.scope_map(text), scopes.scope_map(text, False)
    by, unscoped, outside = scopes.scope_seconds(t, smap)
    per = lambda s: s * 1e3 / requests
    borrowed = defaultdict(float)       # ops that took a neighbour's scope
    for n, a, b in zip(t.names, t.start, t.end):
        if n in own and not own[n]:
            borrowed[f"{n} -> {smap[n]}"] += per((b - a) / 1e9)
    device = defaultdict(float)
    convs = defaultdict(dict)
    for k, v in by.items():
        parts = k.split("/")
        if scopes.CONV.match(k):
            sub = parts[2] if len(parts) > 2 else "rest"
            convs[parts[1]][sub] = convs[parts[1]].get(sub, 0.0) + per(v)
        else:
            device[parts[0]] += per(v)
    device.update(unscoped=per(unscoped), other=per(outside))
    spans = [sp[:3] for sp in host] + list(t.spans)
    gaps = np.array([[a, b] for _, a, b in t.idle_gaps()]).reshape(-1, 2)
    idle = defaultdict(float)
    for lab, (a, b) in zip(scopes.innermost_labels(spans, gaps), gaps):
        idle[lab] += per((b - a) / 1e9)
    client = [(n, a, b, None) for n, a, b in t.spans]
    return {"requests": requests, "window_s": t.window_s,
            "device": dict(device),
            "convs": {k: convs[k] for k in sorted(convs,
                                                  key=lambda c: int(c[4:]))},
            "host": scopes.host_ms(client + host, t.window, requests),
            "idle": dict(idle),
            "borrowed": dict(sorted(borrowed.items(),
                                    key=lambda kv: -kv[1])[:TOP])}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    ap.add_argument("--width", type=float)
    ap.add_argument("--image-size", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from bench import run, scopes, trace as tr
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cell = run.load_cell(ROOT, args.workload)
    cfg = dict(cell.cfg)
    if args.width is not None:
        cfg["width"] = args.width
    if args.image_size is not None:
        cfg["image_size"] = args.image_size
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul"])
    dev = jax.devices()[0]
    run.log(f"device: {dev.platform} {dev.device_kind}")
    server = cell.module("runners", cfg["runner"]).Server(cfg, args.seed)
    client = cell.module("clients", cell.traffic["client"])
    frames = run.make_pool(cfg, cell.traffic, args.seed)
    server.fetch(server.invoke(frames[0]))
    client.run(server, frames, run.picks(cell.traffic, args.seed, len(frames),
                                         0), cell.traffic, run.WARMUP_SECONDS)

    trace_dir = Path(tempfile.mkdtemp(prefix="bench_record_"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        records = client.run(server, frames,
                             run.picks(cell.traffic, args.seed, len(frames), 1),
                             cell.traffic, args.seconds)
    jax.profiler.stop_trace()
    t = time.perf_counter()
    text = scopes.program_text(cfg, server.executor)
    run.log(f"program text: {time.perf_counter() - t:.3f} s")

    args.out.mkdir(parents=True, exist_ok=True)
    xplane = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = args.out / args.workload
    Path(f"{out}.xplane.pb.gz").write_bytes(gzip.compress(xplane.read_bytes()))
    Path(f"{out}.hlo.txt.gz").write_bytes(gzip.compress(text.encode()))
    rep = report(tr.load(trace_dir), text, scopes.host_spans(trace_dir),
                 len(records))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(rep), flush=True)


if __name__ == "__main__":
    main()
