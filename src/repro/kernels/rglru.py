"""RG-LRU linear recurrence as a Pallas TPU kernel.

Sequential over time blocks (grid-carried fp32 VMEM state), parallel over
(batch, width) tiles.  Within a time block the recurrence runs as an
in-kernel ``fori_loop`` — the TPU analogue of the paper's vector-engine
executing a pointwise recurrent update close to the data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, gx_ref, ga_ref, la_ref, h0_ref, o_ref, h_ref, *,
                  bs: int, c: float):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)               # (bb, bs, bw)
    r = jax.nn.sigmoid(ga_ref[...].astype(jnp.float32))
    i = jax.nn.sigmoid(gx_ref[...].astype(jnp.float32))
    log_a = c * r * jax.nn.softplus(la_ref[...].astype(jnp.float32))[None]
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = mult * i * x

    def step(t, h):
        h = a[:, t] * h + b[:, t]                    # (bb, bw)
        o_ref[:, t, :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bb", "bw", "bs", "interpret"))
def rglru_scan(x: jax.Array, gx: jax.Array, ga: jax.Array, log_a: jax.Array,
               h0: jax.Array, *, bb: int = 8, bw: int = 128, bs: int = 64,
               interpret: bool = False) -> jax.Array:
    """x/gx/ga (B, S, W); log_a (W,); h0 (B, W) -> h sequence (B, S, W)."""
    B, S, W = x.shape
    bb, bw, bs = min(bb, B), min(bw, W), min(bs, S)
    assert B % bb == 0 and W % bw == 0 and S % bs == 0
    kernel = functools.partial(_rglru_kernel, bs=bs, c=-8.0)
    blk = lambda ib, iw, it: (ib, it, iw)
    return pl.pallas_call(
        kernel,
        grid=(B // bb, W // bw, S // bs),
        in_specs=[
            pl.BlockSpec((bb, bs, bw), blk),
            pl.BlockSpec((bb, bs, bw), blk),
            pl.BlockSpec((bb, bs, bw), blk),
            pl.BlockSpec((1, bw), lambda ib, iw, it: (0, iw)),
            pl.BlockSpec((bb, bw), lambda ib, iw, it: (ib, iw)),
        ],
        out_specs=pl.BlockSpec((bb, bs, bw), blk),
        out_shape=jax.ShapeDtypeStruct((B, S, W), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(x, gx, ga, log_a.reshape(1, W), h0)
