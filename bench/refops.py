"""Plain ``jax.numpy``/``lax`` building blocks of the references.

Nothing here imports the program.  ``passes=None`` computes every
convolution and matmul at ``Precision.HIGHEST`` (float32 on the TPU);
``passes=3`` computes them as three bfloat16 passes with float32
accumulation (``hi*hi + hi*lo + lo*hi``), which is what ``Precision.HIGH``
does on the TPU, written out so that it means the same on every backend.
It is the control: the nearest precision below the configuration's.

The split rounds with ``reduce_precision``, not a round trip through
bfloat16: XLA may drop ``f32(bf16(x))`` as excess precision, which makes
``lo`` zero and the three passes one.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")


def channels(c: int, width: float) -> int:
    """Channel count at a width multiplier (never below 8)."""
    return max(8, int(c * width))


def init_conv(key, kh: int, kw: int, c: int, o: int) -> jax.Array:
    """He-normal convolution weights, (kh, kw, c, o)."""
    return jax.random.normal(key, (kh, kw, c, o)) * math.sqrt(2.0 / (kh * kw * c))


def _split(x):
    """x = hi + lo + O(2^-16 |x|), hi and lo exact in bfloat16."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _three_pass(op, a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    return op(ah, bh) + (op(ah, bl) + op(al, bh))


def conv(x, w, stride: int, passes: Optional[int] = None):
    """SAME-padded NHWC convolution."""
    def op(a, b, **kw):
        return lax.conv_general_dilated(a, b, (stride, stride), "SAME",
                                        dimension_numbers=_DN, **kw)
    if passes is None:
        return op(x, w, precision=lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be None or 3, not {passes}")
    return _three_pass(lambda a, b: op(a, b, preferred_element_type=jnp.float32),
                       x, w)


def matmul(x, w, passes: Optional[int] = None):
    if passes is None:
        return jnp.dot(x, w, precision=lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be None or 3, not {passes}")
    return _three_pass(lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32),
                       x, w)


def normalize(frames):
    """f1 of the Table I vision pipelines: uint8 pixels to [-1, 1]."""
    return frames.astype(jnp.float32) * jnp.float32(1.0 / 127.5) + jnp.float32(-1.0)


class Conv(NamedTuple):
    """One convolution of a forward pass at batch 1: input ``h x w x cin``,
    kernel ``k x k``, ``cout`` outputs, SAME padding."""
    h: int
    w: int
    cin: int
    cout: int
    k: int
    stride: int

    @property
    def m(self) -> int:      # output pixels: rows of the im2col matrix
        return -(-self.h // self.stride) * -(-self.w // self.stride)

    @property
    def flops(self) -> int:
        return 2 * self.m * self.k * self.k * self.cin * self.cout

    @property
    def bytes(self) -> int:
        """float32 bytes of the input activation, the weights and the
        output: what the convolution itself must move, not its im2col
        patches."""
        return 4 * (self.h * self.w * self.cin
                    + self.k * self.k * self.cin * self.cout
                    + self.m * self.cout)
