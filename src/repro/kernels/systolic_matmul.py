"""The DSA systolic array as a Pallas TPU kernel.

The paper's accelerator is a 128x128 weight-stationary systolic array with
multi-bank scratchpads and a tiling compiler that double-buffers tile DMA
against tile compute (§IV-A).  On TPU this maps 1:1 onto the MXU with
explicit BlockSpec VMEM tiling: the (bm, bk) x (bk, bn) tiles stream through
VMEM while the grid pipeline overlaps the next tile's DMA with the current
tile's matmul — exactly the paper's "overlap memory transfer for a tile with
the computation of the preceding tile".

The paper's Vector Engine (activations / quantization / casting after the
GEMM) is fused into the epilogue on the last K step, so GEMM outputs never
round-trip to HBM — the paper's operator-fusion compiler pass.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation into an fp32
VMEM scratch accumulator).  The caller picks the tiles: ``ops.matmul_tiles``
chooses them from the GEMM's shape, and a tile as large as its dimension may
take any size.  Where the tiles need more VMEM than the scoped default, the
kernel raises its VMEM limit to twice what they take (:func:`tile_vmem_bytes`)
and 4 MiB more.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


# the Mosaic compiler's scoped VMEM limit on a TPU v5e unless a kernel asks
# for more; the core has 128 MiB
SCOPED_VMEM_BYTES = 16 << 20


def tile_vmem_bytes(bm: int, bk: int, bn: int, itemsize: int = 4) -> int:
    """VMEM the kernel's tiles take: x, w, the bias row and the output
    double-buffered, and the float32 accumulator, each block padded to
    whole (8, 128) tiles."""
    def tile(rows, cols):
        return -(-rows // 8) * 8 * -(-cols // 128) * 128
    return (itemsize * (2 * tile(bm, bk) + 2 * tile(bk, bn) + 2 * tile(1, bn)
                        + 2 * tile(bm, bn)) + 4 * tile(bm, bn))


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, act: str,
                   nk: int, out_dtype):
    """One (bm, bn) output tile; accumulate over the K grid dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU: fp32 accumulation of a (bm, bk) x (bk, bn) tile
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        acc = _ACTS[act](acc)
        o_ref[...] = acc.astype(out_dtype)


def _matmul_kernel_no_bias(x_ref, w_ref, o_ref, acc_ref, **kw):
    _matmul_kernel(x_ref, w_ref, None, o_ref, acc_ref, **kw)


@functools.partial(jax.jit, static_argnames=("act", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def systolic_matmul(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                    *, act: str = "none", bm: int = 128, bn: int = 128,
                    bk: int = 128, out_dtype=None,
                    interpret: bool = False) -> jax.Array:
    """(M, K) @ (K, N) [+ b] with fused epilogue.  Dims must tile evenly;
    a tile equal to its whole dimension may have any size."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    out_dtype = out_dtype or x.dtype
    nk = K // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(b.reshape(1, N))

    kernel = functools.partial(
        _matmul_kernel if b is not None else _matmul_kernel_no_bias,
        act=act, nk=nk, out_dtype=out_dtype)
    # Mosaic's float32 matmul splits its operands into bfloat16 parts
    # beside the tiles, up to about 1.6x the x and w tiles again (least
    # limits found by compiling for a described v5e): room for twice the
    # tiles
    tiles = tile_vmem_bytes(bm, bk, bn, jnp.dtype(x.dtype).itemsize)
    vmem = 2 * tiles + (4 << 20)

    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem if vmem > SCOPED_VMEM_BYTES else None),
        interpret=interpret,
        name="systolic_matmul",
    )(*args)
