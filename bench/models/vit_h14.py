"""ViT-H/14 (Dosovitskiy et al. 2020, arXiv:2010.11929, Table 1,
"ViT-Huge") as the Table I ``remote_sensing`` function serves it, in plain
``jax.numpy`` and ``lax``.

The patch embedding is a stride-14 14x14 convolution with bias, computed
as the product of the image's patches with the kernel; the class
token is prepended and a learned position table added; 32 pre-norm blocks
of LayerNorm (eps 1e-6), fused QKV projection with bias, 16-head softmax
attention (scale 1/sqrt(80), float32), output projection, LayerNorm,
MLP with tanh GELU, residual adds; the final LayerNorm, the class token
and a dense head with bias (no pre-logits layer), as in the authors'
release (``google-research/vision_transformer``, ``models_vit.py``).

Weights come from the seed by the served function's recipe:
``split(PRNGKey(seed), 8 + 12 * layers)`` taken in order (patch kernel and
bias, class token, position table; per block ln1 scale and bias, qkv and
its bias, out and its bias, ln2 scale and bias, fc1 and its bias, fc2 and
its bias; the final norm's scale and bias; head and its bias).  Here the
blocks' parameters are stacked on a leading axis of ``layers``.  Kernels
are N(0, 1/fan_in), biases, LayerNorm biases, the class token and the
position table N(0, 0.02^2), LayerNorm scales 1 + N(0, 0.02^2).

Also here: the operation counts (``convs``, ``head_flops``) and the least
times per request that the metrics ``attention_roofline`` and
``dense_roofline`` read (``attention_work``, ``dense_work``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import refops
from bench.refops import Conv, matmul, normalize

# the configuration keys, and their values, at which the CPU (Pallas in
# interpret mode) serves and checks a request in seconds: 2x2 patches
# and the class token, 5 tokens, 16 heads of 8
CPU_SIZE = {"width": 0.1, "image_size": 28}

# the program's named scopes that the metrics read (``bench/scopes.py``)
ATTENTION_SCOPE = r"^f2/block\d+/attn(/|$)"
DENSE_SCOPE = r"^f2/block\d+/(qkv|out|fc1|fc2)(/|$)"


def _sizes(cfg):
    """(width, MLP width, heads, layers, patch) at the configured width."""
    w = cfg["width"]
    return (round(cfg["hidden_size"] * w), round(cfg["mlp_dim"] * w),
            cfg["num_heads"], cfg["num_layers"], cfg["patch_size"])


def _tokens(cfg) -> int:
    g = cfg["image_size"] // cfg["patch_size"]
    return g * g + 1


def init(key, cfg):
    d, f, _, layers, patch = _sizes(cfg)
    keys = jax.random.split(key, 8 + 12 * layers)

    def draws(ks):
        it = iter(ks)
        return lambda shape, scale: jax.random.normal(next(it), shape) * scale

    def dense(normal, k, n):
        return {"w": normal((k, n), k ** -0.5), "b": normal((n,), 0.02)}

    def norm(normal):
        return {"scale": 1.0 + normal((d,), 0.02), "bias": normal((d,), 0.02)}

    def block(ks):
        normal = draws(ks)
        return {"ln1": norm(normal), "qkv": dense(normal, d, 3 * d),
                "out": dense(normal, d, d), "ln2": norm(normal),
                "fc1": dense(normal, d, f), "fc2": dense(normal, f, d)}
    c = cfg["in_channels"]
    grid = cfg["posemb_image_size"] // patch
    normal = draws(keys[:4])
    p = {"patch": {"w": normal((patch, patch, c, d), (patch * patch * c) ** -0.5),
                   "b": normal((d,), 0.02)},
         "cls": normal((1, 1, d), 0.02),
         "pos": normal((1 + grid * grid, d), 0.02)}
    # each block's 12 keys, drawn under vmap (the same values as one by
    # one) and stacked on a leading axis that ``forward`` scans
    per_block = keys[4:4 + 12 * layers]
    p["blocks"] = jax.vmap(block)(
        per_block.reshape(layers, 12, *per_block.shape[1:]))
    normal = draws(keys[4 + 12 * layers:])
    p["norm"] = norm(normal)
    p["head"] = dense(normal, d, cfg["classes"])
    return p


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


def _gelu(x):
    """The tanh form, flax ``nn.gelu``'s default in the release."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _product(spec, a, b, passes):
    """``einsum(spec, a, b)`` at ``HIGHEST``, or in three bfloat16 passes
    (the control, as ``bench.refops`` computes it)."""
    if passes is None:
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be None or 3, not {passes}")
    return refops._three_pass(lambda x, y: jnp.einsum(
        spec, x, y, preferred_element_type=jnp.float32), a, b)


def _posemb(pos, g_new):
    """The table for a ``g_new`` x ``g_new`` grid: the class row, then the
    grid resized linearly with its corners aligned (``scipy.ndimage.zoom``
    of order 1, which the release uses to load a table at another
    resolution), written as an interpolation matrix on each axis."""
    g = math.isqrt(pos.shape[0] - 1)
    if g == g_new:
        return pos
    a = np.zeros((g_new, g))
    for i, x in enumerate(np.linspace(0.0, g - 1, g_new)):
        lo = int(np.floor(x))
        hi = min(lo + 1, g - 1)
        a[i, lo] += 1.0 - (x - lo)
        a[i, hi] += x - lo
    a = jnp.asarray(a, jnp.float32)
    grid = jnp.einsum("ig,jh,ghd->ijd", a, a, pos[1:].reshape(g, g, -1),
                      precision=lax.Precision.HIGHEST)
    return jnp.concatenate([pos[:1], grid.reshape(g_new * g_new, -1)])


def _patches(x, patch):
    """(B, H, W, C) -> (B, H/patch, W/patch, patch*patch*C): the image
    cut into its non-overlapping patches, each flattened in (row, column,
    channel) order, the order of the HWIO kernel's first three axes.  The
    stride-``patch`` convolution is then one product with the kernel as a
    matrix (a ``lax`` convolution at ``HIGHEST`` would take the v5e's
    compiler about 30 s)."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // patch, patch, W // patch, patch, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, H // patch, W // patch, patch * patch * C)


def forward(p, frames, cfg, passes=None):
    """uint8 frames (B, H, W, 3) -> logits (B, classes)."""
    d, _, heads, _, patch = _sizes(cfg)
    hd = d // heads
    x = _patches(normalize(frames), patch)
    B, g = x.shape[0], x.shape[1]
    x = matmul(x.reshape(B, g * g, -1), p["patch"]["w"].reshape(-1, d),
               passes) + p["patch"]["b"]
    x = jnp.concatenate([jnp.broadcast_to(p["cls"], (B, 1, d)), x], axis=1)
    x = x + _posemb(p["pos"], g)
    S = x.shape[1]

    def dense(y, q):
        return matmul(y, q["w"], passes) + q["b"]

    def block(x, blk):
        y = dense(_layer_norm(x, blk["ln1"]), blk["qkv"]).reshape(
            B, S, 3, heads, hd)
        q, k, v = y[:, :, 0], y[:, :, 1], y[:, :, 2]
        s = _product("bqhd,bkhd->bhqk", q, k, passes) / math.sqrt(hd)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        o = _product("bhqk,bkhd->bqhd", w, v, passes).reshape(B, S, d)
        x = x + dense(o, blk["out"])
        y = _gelu(dense(_layer_norm(x, blk["ln2"]), blk["fc1"]))
        return x + dense(y, blk["fc2"]), None
    # one block compiled and scanned, not 32 unrolled: the reference
    # compiles in a fraction of the time
    x, _ = lax.scan(block, x, p["blocks"])
    x = _layer_norm(x, p["norm"])[:, 0]
    return dense(x, p["head"])


def convs(cfg):
    """The patch embedding, the one convolution of a batch-1 forward."""
    d, _, _, _, patch = _sizes(cfg)
    s = cfg["image_size"]
    return [Conv(s, s, cfg["in_channels"], d, patch, patch)]


def dense_work(cfg):
    """``(flops, bytes)`` of each block GEMM of one request (qkv, out,
    fc1, fc2 in every block), float32 input, weights and output."""
    d, f, _, layers, _ = _sizes(cfg)
    m = _tokens(cfg)
    return [(2 * m * k * n, 4 * (m * k + k * n + m * n))
            for _ in range(layers)
            for k, n in ((d, 3 * d), (d, d), (d, f), (f, d))]


def attention_work(cfg):
    """``(flops, bytes)`` of each block's attention of one request: QK^T
    and PV over the real tokens, q, k and v read and the output written
    once in float32."""
    d, _, _, layers, _ = _sizes(cfg)
    s = _tokens(cfg)
    return [(4 * s * s * d, 4 * 4 * s * d)] * layers


def head_flops(cfg):
    """Every product but the patch embedding: the block GEMMs, both
    attention products and the head."""
    d, _, _, _, _ = _sizes(cfg)
    return (sum(fl for fl, _ in dense_work(cfg))
            + sum(fl for fl, _ in attention_work(cfg))
            + 2 * d * cfg["classes"])


def least_seconds(work, peaks) -> float:
    """Least time of ``(flops, bytes)`` items at the chip's bf16 and HBM
    peaks, each bound by the larger."""
    return sum(max(fl / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
               for fl, b in work)
