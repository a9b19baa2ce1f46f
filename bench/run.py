"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (imports, device, weights from the
seed, the request pool, compile, warm-up) is timed as ``setup_s``; then
the cell's client serves requests for ``--seconds``.  With ``--trace 1``
the window runs under the JAX profiler and the cell's per-layer metrics
are read from the trace; otherwise its end-to-end metrics are printed.
Either way the answers of a seeded sample of the window's requests are
compared with the configuration's plain reference, and the last line of
standard output is one JSON object:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"], "checks"}``.

Exits non-zero, with no result line, where JAX finds no accelerator or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                         # noqa: E402
import gc                                               # noqa: E402
import importlib.util                                   # noqa: E402
import json                                             # noqa: E402
import shutil                                           # noqa: E402
import sys                                              # noqa: E402
import tempfile                                         # noqa: E402
from dataclasses import dataclass                       # noqa: E402
from pathlib import Path                                # noqa: E402
from types import SimpleNamespace                       # noqa: E402

import numpy as np                                      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# The traced window is kept short: a trace of every op of a long window
# is large to write and slow to read.
TRACE_SECONDS = 2.0
WARMUP_SECONDS = 0.5
# A lowering is a new program, whether the persistent cache then holds it
# or not; a cache miss is a program compiled.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoDevice(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(root: Path, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)


def load_cell(root: Path, workload: str) -> Cell:
    """Everything the cell ``workload`` of ``<root>/BENCHMARK.json`` names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    wl = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())

    def here(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return Cell(workload, int(wl["chips"]), cfg, traffic,
                here(spec["end_to_end"]), here(spec["per_layer"]), root)


class CompileCounter:
    """Counts JAX lowerings and persistent-cache misses from when it is
    made."""

    def __init__(self):
        import jax
        self.counts = {LOWER_EVENT: 0, MISS_EVENT: 0}

        def on(name, *_a, **_k):
            if name in self.counts:
                self.counts[name] += 1
        self._on = on
        jax.monitoring.register_event_duration_secs_listener(on)
        jax.monitoring.register_event_listener(on)

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on)

    @property
    def lowered(self) -> int:
        return self.counts[LOWER_EVENT]

    @property
    def compiled(self) -> int:
        return self.counts[MISS_EVENT]


def find_devices(chips: int, require_accelerator: bool):
    import jax
    devs = jax.devices()
    if require_accelerator and devs[0].platform == "cpu":
        raise NoDevice("no accelerator: JAX runs on the CPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def make_pool(cfg: dict, traffic: dict, seed: int):
    """``traffic["pool"]`` uint8 frames of the configuration's size, made on
    the device in one call from the seed: data already on the drive."""
    import jax
    import jax.numpy as jnp
    n, s = int(traffic["pool"]), int(cfg["image_size"])
    shape = (n, cfg["batch"], s, s, cfg["in_channels"])

    @jax.jit
    def frames(key):
        px = jax.random.randint(key, shape, 0, 256, dtype=jnp.int32)
        return tuple(px.astype(jnp.uint8))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x706F6F6C)
    return jax.block_until_ready(list(frames(key)))


def picks(traffic: dict, seed: int, pool: int, stream: int):
    """Pool indices, uniform, from the seed (``stream`` keeps warm-up and
    window apart)."""
    if traffic["pick"] != "uniform":
        raise ValueError(f"unknown pick {traffic['pick']!r}")
    rng = np.random.default_rng([seed, stream])
    while True:
        yield from rng.integers(0, pool, size=4096).tolist()


def gap(out: np.ndarray, answer: np.ndarray, ref: np.ndarray) -> float:
    """The widest error of one request, as a share of its largest reference
    output: the worst output error, or the amount by which the reference
    scores the served answer (top-1 per row) below its own best."""
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        return float("inf")
    rows = ref.reshape(-1, ref.shape[-1])
    a = np.asarray(answer).reshape(-1)
    if a.shape[0] != rows.shape[0] or np.any((a < 0) | (a >= rows.shape[1])):
        return float("inf")
    short = rows.max(-1) - rows[np.arange(rows.shape[0]), a]
    scale = np.max(np.abs(ref))
    return float(max(np.max(np.abs(out - ref)), short.max()) / scale)


def reference_gaps(cell: Cell, seed: int, frames: dict, served: list,
                   control: bool = False) -> dict:
    """Run the configuration's plain reference over each distinct sampled
    frame, one at a time, and compare.  ``served`` holds
    ``(pick, output, answer)``.  With ``control``, also read the gap of the
    reference computed in three bfloat16 passes, answering with its own
    top-1, against the same reference."""
    import jax
    model = cell.module("models", cell.cfg["model"])
    cfg = cell.cfg
    params = jax.jit(lambda k: model.init(k, cfg))(jax.random.PRNGKey(seed))
    fwd = jax.jit(lambda p, x: model.forward(p, x, cfg))
    refs = {i: np.asarray(fwd(params, f)) for i, f in frames.items()}
    out = {"worst_gap": max((gap(o, a, refs[i]) for i, o, a in served),
                            default=float("inf"))}
    if control:
        low = jax.jit(lambda p, x: model.forward(p, x, cfg, passes=3))
        worst = 0.0
        for i, f in frames.items():
            y = np.asarray(low(params, f))
            worst = max(worst, gap(y, y.argmax(-1), refs[i]))
        out["control_gap"] = worst
    return out


def _device_memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True, control: bool = False,
             t_start: float | None = None) -> dict:
    """One run of ``cell``; returns the result object (see module doc)."""
    t_start = T_START if t_start is None else t_start
    setup = {"import": time.perf_counter() - t_start}
    t = time.perf_counter()
    import jax
    setup["import"] += time.perf_counter() - t
    t = time.perf_counter()
    devices = find_devices(cell.chips, require_accelerator)
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices)}
    setup["device"] = time.perf_counter() - t
    log(f"device: {device['platform']} {device['kind']} x{device['count']}")
    peaks = None
    if trace:
        table = json.loads((cell.root / "bench" / "peaks.json").read_text())
        if dev0.device_kind not in table:
            raise SystemExit(f"no peaks for device {dev0.device_kind!r} in "
                             "bench/peaks.json")
        peaks = table[dev0.device_kind]

    cfg, traffic = cell.cfg, cell.traffic
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul"])
    counter = CompileCounter()
    runner = cell.module("runners", cfg["runner"])
    client = cell.module("clients", traffic["client"])

    t = time.perf_counter()
    server = runner.Server(cfg, seed)
    setup["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    frames = make_pool(cfg, traffic, seed)
    setup["pool"] = time.perf_counter() - t
    t = time.perf_counter()
    server.fetch(server.invoke(frames[0]))
    setup["first_call"] = time.perf_counter() - t
    t = time.perf_counter()
    client.run(server, frames, picks(traffic, seed, len(frames), 0), traffic,
               WARMUP_SECONDS)
    setup["warmup"] = time.perf_counter() - t
    setup_lowered, setup_compiled = counter.lowered, counter.compiled

    trace_dir = None
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no event per Python call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        seconds = min(seconds, TRACE_SECONDS)
    t_first = time.perf_counter()
    setup_s = t_first - t_start
    setup["other"] = setup_s - sum(setup.values())
    with jax.profiler.TraceAnnotation("window"):
        records = client.run(server, frames,
                             picks(traffic, seed, len(frames), 1), traffic,
                             seconds)
    window_s = records[-1][3] - t_first
    in_window = (counter.lowered - setup_lowered,
                 counter.compiled - setup_compiled)
    counter.close()
    if trace:
        jax.profiler.stop_trace()
    log("set-up split (s): " + json.dumps(
        {k: round(v, 4) for k, v in setup.items()}) +
        f"; {setup_lowered} programs lowered, {setup_compiled} of them "
        "compiled (the rest came from the persistent cache)")
    log(f"in the window: {in_window[0]} programs lowered, {in_window[1]} "
        "compiled (there should be none)")
    device["memory_peak_bytes"] = _device_memory_peak(devices)

    lat = np.array([r[3] - r[1] for r in records])
    log(f"{len(records)} requests in {window_s:.4f} s; latency median "
        f"{np.median(lat) * 1e3:.4f} ms, p95 {np.percentile(lat, 95) * 1e3:.4f}"
        f" ms, max {lat.max() * 1e3:.4f} ms")

    # correctness: a seeded sample of the window's requests, compared with
    # the plain reference once the program's state is freed
    rng = np.random.default_rng([seed, 2])
    k = min(int(cfg["check"]["sample"]), len(records))
    sample = sorted(rng.choice(len(records), size=k, replace=False).tolist())
    served = [(records[j][0], np.asarray(server.output(records[j][5])),
               records[j][4]) for j in sample]
    sampled_frames = {i: np.asarray(frames[i]) for i, _, _ in served}
    failed = sum(np.shape(r[4]) != np.shape(records[0][4]) for r in records)
    data = SimpleNamespace(
        requests=len(records), window_s=window_s, setup_s=setup_s,
        submit=np.array([r[1] for r in records]),
        invoked=np.array([r[2] for r in records]),
        done=np.array([r[3] for r in records]),
        chips=cell.chips, peaks=peaks, cfg=cfg, trace=None)
    server.close()
    del server, frames, records
    gc.collect()

    breakdown = None
    if trace:
        from bench import trace as tr
        data.trace = tr.load(trace_dir, n_devices=cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = data.trace.busy_s()
        device["window_s"] = data.trace.window_s
        breakdown = data.trace.breakdown()
    model = cell.module("models", cfg["model"])
    data.convs = model.convs(cfg)
    data.model_flops = sum(c.flops for c in data.convs) + model.head_flops(cfg)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.module("metrics", m["name"]).read(data)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t = time.perf_counter()
    gaps = reference_gaps(cell, seed, sampled_frames, served, control)
    log(f"reference over {len(sampled_frames)} frames for {len(served)} "
        f"sampled requests: {time.perf_counter() - t:.3f} s")
    limit = float(cfg["check"]["worst_gap_limit"])
    checks = {"worst_gap": {"value": gaps["worst_gap"], "limit": limit},
              "failed": {"value": failed, "limit": 0}}
    correct = bool(served) and gaps["worst_gap"] <= limit and failed == 0
    result = {"correct": correct, "attempted": len(lat), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control_gap"] = gaps["control_gap"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    cell = load_cell(ROOT, args.workload)
    import jax
    from repro.jax_cache import use_compile_cache
    use_compile_cache()
    # small programs too, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
