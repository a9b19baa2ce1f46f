"""Shared set-up of the benchmark's tests: ``bench`` imports from the
checkout's root, and ``tiny_root`` makes a copy of the benchmark whose
configurations are cut to the size their model files give for the CPU
(``CPU_SIZE``), so that a configuration brings its own size and no test
names a model."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.run import load_module                        # noqa: E402

# the check compares a sample of 8 requests on the CPU
CPU_SAMPLE = 8


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def config(name: str, root: Path = ROOT, cpu: bool = False) -> dict:
    """The configuration ``name`` of ``<root>/BENCHMARK.json`` as its file
    holds it; with ``cpu``, cut to its model file's ``CPU_SIZE``."""
    entry = {c["name"]: c for c in spec(root)["configs"]}[name]
    cfg = json.loads((root / entry["file"]).read_text())
    if cpu:
        cfg.update(load_module(root, "models", cfg["model"]).CPU_SIZE)
    return cfg


def make_tiny_root(src: Path, dst: Path) -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` from ``src`` to ``dst`` with
    every configuration cut to its ``CPU_SIZE`` and checked on
    ``CPU_SAMPLE`` requests, and the CPU in the peaks table so that traced
    runs can be read."""
    shutil.copytree(src / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec(src)
    for c in bench["configs"]:
        cfg = config(c["name"], src, cpu=True)
        cfg["check"] = {**cfg["check"], "sample": CPU_SAMPLE}
        write_json(dst / c["file"], cfg)
    peaks = json.loads((src / "bench" / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    write_json(dst / "bench" / "peaks.json", peaks)
    write_json(dst / "BENCHMARK.json", bench)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root holding the benchmark cut to the CPU's sizes."""
    return make_tiny_root(ROOT, tmp_path)
