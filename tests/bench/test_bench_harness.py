"""The harness end to end on the CPU at tiny sizes: found by name, no
fallback to the CPU, and ``correct`` false when the served path is
broken underneath."""
import gzip
import json
import os
import shutil
import subprocess
import sys
import time
from dataclasses import replace

import jax.numpy as jnp
import pytest

from conftest import ROOT, spec, write_json

from bench import run
from bench import trace as tr

WHOLE_STEP = {"step_mfu", "device_idle_share", "invoke_host_ms"}


def _run(root, workload, seed=2**31 + 5, trace=False, seconds=0.3):
    cell = run.load_cell(root, workload)
    return run.run_cell(cell, seed, seconds, trace, require_accelerator=False,
                        t_start=time.perf_counter())


def test_new_config_traffic_and_metric_are_found_by_name(tiny_root):
    """A configuration, a traffic mix and a metric added as files plus
    entries in BENCHMARK.json run with no other change."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (tiny_root / "bench/configs/resnet50-asset_damage.json").read_text())
    cfg["name"] = "tiny-resnet"
    write_json(tiny_root / "bench/configs/tiny-resnet.json", cfg)
    traffic = json.loads(
        (tiny_root / "bench/traffic/closed1.json").read_text())
    write_json(tiny_root / "bench/traffic/closed2.json",
               {**traffic, "in_flight": 2})
    (tiny_root / "bench/metrics/latency_max_ms.py").write_text(
        "def read(run):\n    return float((run.done - run.submit).max()) * 1e3\n")
    spec["configs"].append({"name": "tiny-resnet", "source": "test",
                            "file": "bench/configs/tiny-resnet.json",
                            "reduced": ["width"], "why": "test"})
    spec["workloads"].append({"name": "tiny-c2", "config": "tiny-resnet",
                              "traffic": "closed2", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "latency_max_ms", "unit": "ms",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny-c2"]})
    write_json(tiny_root / "BENCHMARK.json", spec)

    res = _run(tiny_root, "tiny-c2")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"req_per_s", "latency_p95_ms", "setup_s",
                                   "latency_max_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics_it_can_read(tiny_root):
    res = _run(tiny_root, "yolov3-c1", trace=True)
    assert res["correct"], res["checks"]
    # the CPU trace has no device plane: only the host-clock metrics and
    # the arithmetic ones are there, never a zero for a device share
    assert set(res["metrics"]) == {"invoke_host_ms", "step_mfu"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_eight_in_flight_cell_is_correct(tiny_root):
    """``resnet50-c8`` keeps 8 requests in flight: more than 8 are served
    in a window, every sampled one agrees with the reference."""
    res = _run(tiny_root, "resnet50-c8", seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 8 and res["failed"] == 0


@pytest.mark.parametrize("workload", [w["name"] for w in spec()["workloads"]])
def test_every_cell_reports_the_whole_step_metrics(workload):
    """Every cell, also one that a later PR adds, is given the whole
    step's share of the peak, the device's idle share and the host's
    invocation time among its per-layer metrics."""
    names = {m["name"] for m in run.load_cell(ROOT, workload).per_layer}
    assert WHOLE_STEP <= names, names


def test_traced_eight_in_flight_run_reads_the_whole_step_metrics(
        tiny_root, monkeypatch, tmp_path):
    """A traced ``resnet50-c8`` run reads the three whole-step metrics.
    The CPU's trace has no device plane, so the run is handed a recorded
    chip trace in its place (``tests/bench/data/yolov3_tiny.xplane.pb.gz``,
    another program's): only that each is read, and in its range, is
    checked here."""
    path = tmp_path / "yolov3_tiny.xplane.pb"
    path.write_bytes(gzip.decompress(
        (ROOT / "tests/bench/data/yolov3_tiny.xplane.pb.gz").read_bytes()))
    recorded = tr.load(path)
    monkeypatch.setattr(tr, "load", lambda *a, **k: recorded)
    res = _run(tiny_root, "resnet50-c8", trace=True)
    assert res["correct"], res["checks"]
    assert WHOLE_STEP <= set(res["metrics"]), res["metrics"]
    assert all(res["metrics"][m]["value"] > 0 for m in WHOLE_STEP)
    assert res["metrics"]["device_idle_share"]["value"] < 100


FAULTS = {
    # the top-1 answer altered where f3 produces it
    "answer": lambda rep, prev: replace(
        rep, result=(rep.result + 1) % rep.output.shape[-1]),
    # one output of f2 altered where it is produced
    "output": lambda rep, prev: replace(
        rep, output=rep.output.at[..., 0].add(
            1e-3 * jnp.max(jnp.abs(rep.output)))),
    # the previous request's answer returned for this one
    "stale": lambda rep, prev: prev or rep,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_served_path_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.core.executor import DSCSExecutor
    call = DSCSExecutor.__call__
    last = []

    def broken(self, request):
        rep = FAULTS[fault](call(self, request), last[-1] if last else None)
        last.append(rep)
        return rep
    monkeypatch.setattr(DSCSExecutor, "__call__", broken)
    res = _run(tiny_root, "resnet50-c1")
    assert not res["correct"]
    c = res["checks"]["worst_gap"]
    assert c["value"] > c["limit"]


def test_no_accelerator_exits_nonzero_with_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "resnet50-c1", "--seed", "3", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    has no program to serve."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, *spec["command"][1:], "--workload",
                        "resnet50-c1", "--seed", "3", "--seconds", "1"],
                       cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
