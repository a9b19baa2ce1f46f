"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the whole library (tests, smoke
runs, examples) exercises the kernel bodies on CPU; on a real TPU backend
the same calls compile to Mosaic.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels.systolic_matmul import systolic_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.vector_engine import (fused_affine_act, quantize_int8,
                                         dequantize_int8)
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd import ssd_scan
from repro.kernels.lindley import increments, lindley_scan


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def matmul(x, w, b=None, *, act="none", bm=128, bn=128, bk=128,
           out_dtype=None, interpret=None):
    return systolic_matmul(x, w, b, act=act, bm=bm, bn=bn, bk=bk,
                           out_dtype=out_dtype,
                           interpret=_interpret_default()
                           if interpret is None else interpret)


def matmul_padded(x, w, b=None, *, act="none", bm=128, bn=128, bk=128,
                  out_dtype=None, interpret=None):
    """``matmul`` for arbitrary shapes: zero-pads (M, K, N) to tile
    multiples — the DSA compiler's padding pass (§V)."""
    import jax.numpy as jnp
    M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    Mp = -(-M // bm) * bm
    Kp = -(-K // bk) * bk
    Np = -(-N // bn) * bn
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    bp = jnp.pad(b, (0, Np - N)) if b is not None else None
    out = matmul(xp, wp, bp, act=act, bm=bm, bn=bn, bk=bk,
                 out_dtype=out_dtype, interpret=interpret)
    return out[:M, :N]


def attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
              interpret=None):
    return flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                           bk=bk, interpret=_interpret_default()
                           if interpret is None else interpret)


def affine_act(x, scale, bias, *, act="none", out_dtype=None, bm=256,
               interpret=None):
    return fused_affine_act(x, scale, bias, act=act, out_dtype=out_dtype, bm=bm,
                            interpret=_interpret_default()
                            if interpret is None else interpret)


def quantize(x, *, interpret=None):
    return quantize_int8(x, interpret=_interpret_default()
                         if interpret is None else interpret)


def dequantize(q, scales, *, out_dtype=None, interpret=None):
    import jax.numpy as jnp
    return dequantize_int8(q, scales, out_dtype=out_dtype or jnp.float32,
                           interpret=_interpret_default()
                           if interpret is None else interpret)


def rglru(x, gx, ga, log_a, h0, *, interpret=None):
    return rglru_scan(x, gx, ga, log_a, h0, interpret=_interpret_default()
                      if interpret is None else interpret)


def ssd(x, dt, A, Bm, Cm, *, chunk=128, interpret=None):
    return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                    interpret=_interpret_default()
                    if interpret is None else interpret)


def lindley(t, s, *, br=128, bd=128, interpret=None):
    """Batched FCFS service starts: arrivals ``t`` and services ``s``
    (R, W) float64 -> starts (R, W) float64 numpy.

    The host forms the increments and the starts in float64; the device
    carries the waiting time in float32, within the bound of
    :func:`repro.kernels.lindley.error_bound`.
    """
    t = np.asarray(t, dtype=np.float64)
    a = increments(t, np.asarray(s, dtype=np.float64)).astype(np.float32)
    w = lindley_scan(a, br=br, bd=bd, interpret=_interpret_default()
                     if interpret is None else interpret)
    return t + np.asarray(w, dtype=np.float64)
