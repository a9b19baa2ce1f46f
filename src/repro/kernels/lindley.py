"""Lindley waiting-time recurrence as a Pallas TPU kernel, in float32.

Solves a batch of independent FCFS queues.  For a queue with arrivals
``t`` and service demands ``s`` the waiting time obeys Lindley's
recurrence::

    w_0 = 0,    w_d = max(0, w_{d-1} + a_d),    a_d = s_{d-1} - (t_d - t_{d-1})

and the service start is ``start_d = t_d + w_d``.  The host forms the
increments ``a`` in float64 (:func:`increments`) and rounds them to
float32; the kernel carries ``w`` in float32; the host adds it back to
the float64 arrivals.  The device never sees absolute simulated time, so
its rounding error grows with the queue's backlog inside one busy period
and is wiped at every idle start (where both ``max``es clamp to an exact
0), not with the clock.  TPUs have no float64, and an fp32 cumulative
sum of service over a 10^4-deep queue would lose milliseconds.

Error bound (what :func:`error_bound` evaluates).  With ``u = 2^-24``
(float32 round-to-nearest) and ``W`` the exact waiting times, the
device's waiting time ``w~`` satisfies ``|w~_d - W_d| <= E_d`` where
``E_0 = 0`` and, for ``d >= 1``::

    delta_d = u * (W_{d-1} + E_{d-1} + 2.01 * |a_d|) + 8 * u64 * (t_d + s_{d-1})
    E_d     = 0                    if W_{d-1} + a_d + E_{d-1} + delta_d <= 0
              E_{d-1} + delta_d    otherwise

(``u * 2.01 * |a_d|`` covers rounding ``a_d`` to float32 and the add;
``u * (W + E)`` the add's rounding of the carried value; the ``u64 =
2^-53`` term the float64 forming of ``a_d``.  The reset is exact: when
even the perturbed pre-clamp value is <= 0, both sides clamp to 0.)  The
start then obeys ``|start_device - start_exact| <= B_d`` with::

    B_d = E_d + 2 * (d + 2) * u64 * (t_d + C_d)

where ``C_d`` is the queue's cumulative service up to ``d``; the second
term bounds float64 rounding on either side, so ``B_d`` also bounds the
distance to the float64 numpy backends of :mod:`repro.core.lindley`.
Pinned against that oracle in ``tests/test_kernels.py``.

Layout: rows (queues) ride the lane dimension and the depth axis is
scanned sequentially across grid blocks, carrying ``w`` per lane in a
VMEM scratch.  Zero-padded tails are harmless: position ``d`` depends
only on positions ``<= d`` of the same row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

U32 = 2.0 ** -24      # float32 unit roundoff
U64 = 2.0 ** -53      # float64 unit roundoff


def _lindley_kernel(a_ref, o_ref, w_ref, *, bd: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        w_ref[...] = jnp.zeros_like(w_ref)

    def step(d, w):
        w = jnp.maximum(w + a_ref[pl.ds(d, 1), :], 0.0)
        o_ref[pl.ds(d, 1), :] = w
        return w

    w_ref[...] = jax.lax.fori_loop(0, bd, step, w_ref[...])


@functools.partial(jax.jit, static_argnames=("br", "bd", "interpret"))
def lindley_scan(a: jax.Array, *, br: int = 128, bd: int = 128,
                 interpret: bool = False) -> jax.Array:
    """a (R, W) float32 increments: R queues, depth W (zero pad past each
    queue's length) -> waiting times (R, W) float32."""
    R, W = a.shape
    br, bd = min(br, R), min(bd, W)
    Rp = -(-R // br) * br
    Wp = -(-W // bd) * bd
    # transpose to (depth, rows): rows on lanes, depth scanned
    ap = jnp.pad(a, ((0, Rp - R), (0, Wp - W))).T
    blk = lambda ir, it: (it, ir)
    out = pl.pallas_call(
        functools.partial(_lindley_kernel, bd=bd),
        grid=(Rp // br, Wp // bd),
        in_specs=[pl.BlockSpec((bd, br), blk)],
        out_specs=pl.BlockSpec((bd, br), blk),
        out_shape=jax.ShapeDtypeStruct((Wp, Rp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lindley_scan",
    )(ap)
    return out.T[:R, :W]


def increments(t: np.ndarray, s: np.ndarray) -> np.ndarray:
    """float64 Lindley increments ``a_d = s_{d-1} - (t_d - t_{d-1})`` of
    each row of ``t``/``s`` (R, W); ``a_0 = 0``."""
    a = np.zeros_like(t)
    np.subtract(s[:, :-1], np.diff(t, axis=1), out=a[:, 1:])
    return a


def error_bound(t: np.ndarray, s: np.ndarray) -> np.ndarray:
    """Bound ``B`` (R, W) on ``|start_device - start_exact|`` for queues
    ``t``/``s`` (R, W) float64 — the recurrence in the module docstring,
    vectorized over rows (one step per depth position)."""
    t = np.asarray(t, dtype=np.float64)
    s = np.asarray(s, dtype=np.float64)
    R, W = t.shape
    a = increments(t, s)
    E = np.zeros((R, W))
    w_prev = np.zeros(R)          # exact W_{d-1}, float64
    e_prev = np.zeros(R)
    for d in range(1, W):
        pre = w_prev + a[:, d]
        delta = (U32 * (w_prev + e_prev + 2.01 * np.abs(a[:, d]))
                 + 8 * U64 * (np.abs(t[:, d]) + np.abs(s[:, d - 1])))
        e_prev = np.where(pre + e_prev + delta <= 0.0, 0.0, e_prev + delta)
        E[:, d] = e_prev
        w_prev = np.maximum(pre, 0.0)
    C = np.cumsum(np.abs(s), axis=1)
    depth = np.arange(W, dtype=np.float64)
    return E + 2.0 * (depth + 2.0) * U64 * (np.abs(t) + C)
