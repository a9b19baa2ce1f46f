"""Device time per request in the convolutions' ``im2col`` scopes: the
patches and their reshape to an (M, K) matrix, summed over every
convolution and the traced window's requests (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    got = scopes.for_run(run)
    if got is None:
        return None
    s = sum(v for k, v in got[0].items()
            if scopes.CONV.match(k) and k.endswith("/im2col"))
    return s / run.requests * 1e3 if s > 0 else None
