"""Device time per request of the ViT blocks' attention: every op in an
``f2/block<i>/attn`` scope (the split of the fused projections into
heads, the flash kernel, the merge of the heads), summed over the blocks
and the traced window's requests (``bench/scopes.py``).  Nothing where
the configuration's model file names no attention scope."""
import re
from pathlib import Path

from bench import scopes
from bench.run import load_module


def read(run):
    model = load_module(Path(__file__).resolve().parents[2], "models",
                        run.cfg["model"])
    pattern = getattr(model, "ATTENTION_SCOPE", None)
    got = scopes.for_run(run) if pattern else None
    if got is None:
        return None
    s = sum(v for k, v in got[0].items() if re.match(pattern, k))
    return s / run.requests * 1e3 if s > 0 else None
