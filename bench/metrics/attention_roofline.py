"""Share of the ViT attention's device time that its own work needs at
the chip's peaks: per block the larger of 4*S^2*d / bf16 peak and the
bytes of q, k, v and the output (4 * 4*S*d) / HBM bandwidth, S the real
tokens, summed over the blocks and the traced requests, over the time of
every op in the ``f2/block<i>/attn`` scopes (``attention_work`` in the
model file; ``bench/scopes.py``).  Nothing where the model file has no
such count."""
import re
from pathlib import Path

from bench import scopes
from bench.run import load_module


def read(run):
    if run.peaks is None:
        return None
    model = load_module(Path(__file__).resolve().parents[2], "models",
                        run.cfg["model"])
    if not hasattr(model, "attention_work"):
        return None
    got = scopes.for_run(run)
    if got is None:
        return None
    t = sum(v for k, v in got[0].items()
            if re.match(model.ATTENTION_SCOPE, k))
    if t <= 0:
        return None
    least = model.least_seconds(model.attention_work(run.cfg), run.peaks)
    return 100.0 * least * run.requests / t
