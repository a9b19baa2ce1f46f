"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the whole library (tests, smoke
runs, examples) exercises the kernel bodies on CPU; on a real TPU backend
the same calls compile to Mosaic.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels.systolic_matmul import systolic_matmul, tile_vmem_bytes
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


# The tile rule of ``matmul_tiles``: the VMEM its tiles may take
# (``tile_vmem_bytes``), the most rows in a tile, and the grid steps a call
# should keep, so that the next weight tile's DMA overlaps this tile's matmul
TILE_VMEM_BUDGET = 24 << 20
MAX_ROW_TILE = 1024
MIN_GRID_STEPS = 4


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row_tile(M: int) -> int:
    """Rows per tile: all of M in one tile where it fits; else M split into
    as few tiles as ``MAX_ROW_TILE`` allows, exactly where a split into
    whole multiples of 8 of at least 128 rows exists, else evenly with
    the last tile padded."""
    if M <= MAX_ROW_TILE:
        return M
    t = -(-M // MAX_ROW_TILE)
    if M % 8 == 0:
        for n in range(t, M // 128 + 1):
            if (M // 8) % n == 0:
                return M // n
    return _round_up(-(-M // t), 8)


def matmul_tiles(M: int, K: int, N: int) -> tuple:
    """``(bm, bk, bn)`` for an (M, K) @ (K, N) call of the systolic kernel,
    from its shape alone.

    ``bm`` covers M in as few row tiles as ``MAX_ROW_TILE`` allows
    (:func:`_row_tile`).  ``bk`` is the whole of K where that fits the
    VMEM budget beside a 128-wide weight tile, so the x tile stays put while
    the weight tiles stream past it; else the largest multiple of 128 that
    divides K padded to 128 and fits.  ``bn`` is the whole of N where N is
    not a multiple of 128 and fits (no padded column); else the largest
    multiple of 128 that divides N padded to 128, fits, and leaves
    ``MIN_GRID_STEPS`` grid steps, or the smallest that fits where none
    does.  A tile equal to its whole dimension needs no padding."""
    bm = _row_tile(M)
    n_rows = -(-M // bm)

    def fits(bk, bn):
        return tile_vmem_bytes(bm, bk, bn) <= TILE_VMEM_BUDGET

    if fits(K, min(N, 128)):
        bk = K
    else:
        Kp = _round_up(K, 128)
        bk = max((d for d in range(128, Kp, 128)
                  if Kp % d == 0 and fits(d, min(N, 128))), default=128)
    nk = -(-K // bk)
    if N % 128 and fits(bk, N):
        return bm, bk, N
    Np = _round_up(N, 128)
    widths = [d for d in range(128, Np + 1, 128)
              if Np % d == 0 and fits(bk, d)]
    enough = [d for d in widths if n_rows * (Np // d) * nk >= MIN_GRID_STEPS]
    return bm, bk, max(enough) if enough else min(widths, default=128)


def matmul_padded(x, w, b=None, *, act="none", bm=None, bn=None, bk=None,
                  out_dtype=None, interpret=None):
    """``matmul`` for arbitrary shapes — the DSA compiler's padding pass
    (§V).  Tiles left out come from :func:`matmul_tiles`; each dimension is
    zero-padded to a whole number of its tiles and the result cut back to
    (M, N)."""
    import jax.numpy as jnp
    M, K = x.shape
    N = w.shape[1]
    tm, tk, tn = matmul_tiles(M, K, N)
    bm, bk, bn = min(bm or tm, M), min(bk or tk, K), min(bn or tn, N)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
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
