"""Blocked (flash-style) attention as a Pallas TPU kernel.

Online-softmax over K/V blocks with fp32 VMEM accumulators; supports GQA
(kv-head groups via BlockSpec index maps), causal masking, sliding
windows and lengths that are not a multiple of the block (keys padded up
to a whole block get no weight; padded query rows are cut off).  Grid:
(batch*heads, Sq/bq, Skv/bk) with the K/V dimension innermost and
sequential — the same tiling the pure-JAX
``models.layers.blocked_attention`` oracle uses, so the two validate against
each other across shapes/dtypes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, kv_len: int,
                  bq: int, bk: int, nk: int, out_dtype):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    if kv_len:                  # keys padded past the true length
        mask &= kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    """q (B, H, Sq, D); k/v (B, KV, Skv, D) -> (B, H, Sq, D), any lengths
    and head dim.  A block as long as its sequence takes it whole; a
    shorter one that does not divide it is padded up to whole blocks."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(bq, Sq), min(bk, Skv)
    Sqp, Skp = -(-Sq // bq) * bq, -(-Skv // bk) * bk
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Skv:
        pad = ((0, 0), (0, 0), (0, Skp - Skv), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    nk = Skp // bk
    scale = 1.0 / math.sqrt(D)

    qr = q.reshape(B * H, Sqp, D)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window,
                               kv_len=Skv if Skp != Skv else 0,
                               bq=bq, bk=bk, nk=nk, out_dtype=q.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sqp // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda bh, iq, ik, H=H, G=G: (bh // H, (bh % H) // G,
                                                       ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda bh, iq, ik, H=H, G=G: (bh // H, (bh % H) // G,
                                                       ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qr, k, v)
    return out.reshape(B, H, Sqp, D)[:, :, :Sq]
