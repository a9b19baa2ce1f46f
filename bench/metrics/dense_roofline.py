"""Share of the ViT block GEMMs' device time that their own work needs at
the chip's peaks: per GEMM (``qkv``, ``out``, ``fc1``, ``fc2``, M the
real tokens) the larger of 2*M*K*N / bf16 peak and its float32 input,
weights and output bytes / HBM bandwidth, summed over the blocks and the
traced requests, over the time of every op in those scopes (padding,
kernel, slicing), so that it reads the same work whatever implements the
GEMMs (``dense_work`` in the model file; ``bench/scopes.py``).  Nothing
where the model file has no such count."""
import re
from pathlib import Path

from bench import scopes
from bench.run import load_module


def read(run):
    if run.peaks is None:
        return None
    model = load_module(Path(__file__).resolve().parents[2], "models",
                        run.cfg["model"])
    if not hasattr(model, "dense_work"):
        return None
    got = scopes.for_run(run)
    if got is None:
        return None
    t = sum(v for k, v in got[0].items() if re.match(model.DENSE_SCOPE, k))
    if t <= 0:
        return None
    least = model.least_seconds(model.dense_work(run.cfg), run.peaks)
    return 100.0 * least * run.requests / t
