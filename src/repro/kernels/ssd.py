"""Mamba-2 SSD (state-space duality) chunk kernel.

One grid cell processes one (batch, head) x chunk tile: the intra-chunk
quadratic term runs on the MXU ((Q,Q) and (Q,N) matmuls inside VMEM), the
inter-chunk state is carried in an fp32 VMEM scratch across the sequential
chunk grid dimension — the Pallas analogue of ``models.layers.ssd_chunked``
(its associative-scan formulation is the pure-jnp oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, hout_ref,
                state_ref, *, Q: int, nc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)                 # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)               # (Q,)
    a = a_ref[0, 0].astype(jnp.float32)              # scalar (negative)
    b = b_ref[0].astype(jnp.float32)                 # (Q, N)
    c = c_ref[0].astype(jnp.float32)                 # (Q, N)

    dA = dt * a
    cum = jnp.cumsum(dA)
    seg = cum[-1]

    # intra-chunk (quadratic within Q)
    Li = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    CB = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    W = jnp.where(tri, jnp.exp(Li) * CB, 0.0) * dt[None, :]
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)

    # state update
    w = dt * jnp.exp(seg - cum)                      # (Q,)
    state_ref[...] = jnp.exp(seg) * state_ref[...] + jax.lax.dot_general(
        x, b * w[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _final():
        hout_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 128, interpret: bool = False):
    """x (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,G,N).

    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    xb = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtb = dt.transpose(0, 2, 1).reshape(B * H, S)
    bb = Bm.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    cb = Cm.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    a2 = A.reshape(H, 1)

    kernel = functools.partial(_ssd_kernel, Q=Q, nc=nc)
    grp = lambda bh, H=H, G=G, rep=rep: (bh // H) * G + ((bh % H) // rep)
    y, hfin = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Q), lambda bh, ic: (bh, ic)),
            pl.BlockSpec((1, 1), lambda bh, ic, H=H: (bh % H, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ic, grp=grp: (grp(bh), ic, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ic, grp=grp: (grp(bh), ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xb, dtb, a2, bb, cb)
    return (y.reshape(B, H, S, P).transpose(0, 2, 1, 3),
            hfin.reshape(B, H, P, N))
