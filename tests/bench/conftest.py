"""Shared set-up of the benchmark's tests: ``bench`` imports from the
checkout's root, and ``tiny_root`` makes a copy of the benchmark whose
configurations are cut to a size the CPU runs in seconds."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# width and image size at which the CPU (Pallas in interpret mode) serves
# a request in milliseconds; the check compares a sample of 8
TINY = {"resnet50": {"width": 0.125, "image_size": 32},
        "yolov3": {"width": 0.125, "image_size": 64}}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root holding ``BENCHMARK.json`` and a copy of ``bench/``
    with every configuration cut to its ``TINY`` size, and the CPU in the
    peaks table so that traced runs can be read."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(TINY[cfg["model"]])
        cfg["check"] = {**cfg["check"], "sample": 8}
        write_json(tmp_path / c["file"], cfg)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    write_json(tmp_path / "bench" / "peaks.json", peaks)
    write_json(tmp_path / "BENCHMARK.json", spec)
    return tmp_path
