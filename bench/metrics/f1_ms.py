"""Device time per request in the f1 scope: the vector-engine kernel
(``fused_affine_act``) with the casts and reshapes around it, summed over
the traced window's requests.  Ops are put down to scopes by the compiled
program's metadata (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    got = scopes.for_run(run)
    if got is None:
        return None
    s = sum(v for k, v in got[0].items() if k.split("/")[0] == "f1")
    return s / run.requests * 1e3 if s > 0 else None
