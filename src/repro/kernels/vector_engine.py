"""The DSA Vector Engine as Pallas kernels.

The paper's SIMD unit executes activation functions, quantization, datatype
casting and simple pre/post-processing after the systolic array (§IV-A).
On TPU these are VPU (8x128-lane) ops; we expose the three canonical
patterns:

  fused_affine_act : y = act(x * scale + bias), cast  (the GEMM epilogue /
                     normalization-style pre-processing)
  quantize_int8    : per-row symmetric int8 quantization (+ fp32 scales)
  dequantize_int8  : back to float
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.systolic_matmul import _ACTS


def _affine_kernel(x_ref, s_ref, b_ref, o_ref, *, act, out_dtype):
    x = x_ref[...].astype(jnp.float32)
    y = x * s_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = _ACTS[act](y).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("act", "out_dtype", "bm",
                                             "interpret"))
def fused_affine_act(x: jax.Array, scale: jax.Array, bias: jax.Array, *,
                     act: str = "none", out_dtype=None, bm: int = 256,
                     interpret: bool = False) -> jax.Array:
    """x (M, N); scale/bias (N,) broadcast per column."""
    M, N = x.shape
    bm = min(bm, M)
    assert M % bm == 0
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        functools.partial(_affine_kernel, act=act, out_dtype=out_dtype),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="fused_affine_act",
    )(x, scale.reshape(1, N), bias.reshape(1, N))


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def quantize_int8(x: jax.Array, *, bm: int = 256, interpret: bool = False):
    """x (M, N) -> (int8 (M, N), fp32 row scales (M, 1))."""
    M, N = x.shape
    bm = min(bm, M)
    assert M % bm == 0
    return pl.pallas_call(
        _quant_kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, N), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        interpret=interpret,
        name="quantize_int8",
    )(x)


def _dequant_kernel(q_ref, s_ref, o_ref, *, out_dtype):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "bm", "interpret"))
def dequantize_int8(q: jax.Array, scales: jax.Array, *, out_dtype=jnp.float32,
                    bm: int = 256, interpret: bool = False) -> jax.Array:
    M, N = q.shape
    bm = min(bm, M)
    assert M % bm == 0
    return pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype=out_dtype),
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="dequantize_int8",
    )(q, scales)
