"""The paper's Table I vision models in JAX (ResNet-50, EfficientNet-B0-ish,
FCN, YOLOv3, ViT-H/14), structurally faithful at published widths with a
``width`` multiplier for CPU-scale smoke/demo runs.

Convolutions can execute through the DSA path: im2col patches ->
``kernels.ops.matmul`` (the systolic kernel) — the paper's compiler story.
ViT's dense layers run on the same kernel and its attention on the flash
kernel.
The patches are pure data movement (a pad and ``kh*kw`` strided slices,
ordered ``(kh, kw, C)`` along K), so the HWIO weights are the (K, O)
matrix as they lie.
"""
from __future__ import annotations

import itertools
import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Pytree = Any


def im2col(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """SAME-padded patches of x (B,H,W,C) as (B,H',W',kh*kw*C), ordered
    (kh, kw, C) along the last axis: x padded once, then one strided slice
    per kernel tap, concatenated.  Copies only, so exact in any precision."""
    B, H, W, C = x.shape
    (pt, pb), (pl, pr) = lax.padtype_to_pads((H, W), (kh, kw),
                                             (stride, stride), "SAME")
    xp = lax.pad(x, jnp.zeros((), x.dtype),
                 ((0, 0, 0), (pt, pb, 0), (pl, pr, 0), (0, 0, 0)))
    H2, W2 = -(-H // stride), -(-W // stride)
    taps = [lax.slice(xp, (0, i, j, 0),
                      (B, i + (H2 - 1) * stride + 1,
                       j + (W2 - 1) * stride + 1, C),
                      (1, stride, stride, 1))
            for i in range(kh) for j in range(kw)]
    return lax.concatenate(taps, 3)


def conv2d(x: jax.Array, w: jax.Array, stride: int = 1,
           use_kernel: bool = False, *, name: str,
           b: jax.Array | None = None) -> jax.Array:
    """x (B,H,W,C); w (kh,kw,C,O), SAME padding, plus the bias ``b`` (O,)
    if given, in the named scope ``name``.  On the kernel path its steps
    have scopes of their own: ``im2col`` (:func:`im2col`'s patches as an
    (M, K) matrix), ``weights`` (``w`` as (K, O): a reshape, since the
    patches share the HWIO order) and ``gemm`` (the padded systolic call,
    the bias in its epilogue)."""
    with jax.named_scope(name):
        if not use_kernel:
            y = lax.conv_general_dilated(
                x, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return y if b is None else y + b
        kh, kw, c, o = w.shape
        with jax.named_scope("im2col"):
            patches = im2col(x, kh, kw, stride)
            B, H2, W2, K = patches.shape
            patches = patches.reshape(B * H2 * W2, K)
        from repro.kernels import ops
        with jax.named_scope("weights"):
            w2 = w.reshape(K, o)
        with jax.named_scope("gemm"):
            return ops.matmul_padded(patches, w2, b).reshape(B, H2, W2, o)


def _numbered(use_kernel: bool):
    """``conv2d`` for one forward pass, each call in the scope ``conv<i>``,
    ``i`` counting the calls in program order."""
    n = itertools.count()
    return lambda x, w, stride, b=None: conv2d(x, w, stride, use_kernel,
                                               name=f"conv{next(n)}", b=b)


def _init_conv(key, kh, kw, c, o):
    fan = kh * kw * c
    return jax.random.normal(key, (kh, kw, c, o)) * math.sqrt(2.0 / fan)


def batch_norm(x, scale, bias, eps=1e-5):
    m = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    v = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * scale + bias


# --------------------------------------------------------------------------
# ResNet-50 (bottleneck), width-scalable
# --------------------------------------------------------------------------

def resnet50_init(key, *, width: float = 1.0, classes: int = 1000) -> Pytree:
    ks = jax.random.split(key, 256)
    it = iter(range(256))
    w = lambda c: max(8, int(c * width))
    p: Dict[str, Any] = {"stem": _init_conv(ks[next(it)], 7, 7, 3, w(64))}
    spec = [(3, 64, 256), (4, 128, 512), (6, 256, 1024), (3, 512, 2048)]
    cin = w(64)
    blocks = []
    for i, (n, mid, out) in enumerate(spec):
        for j in range(n):
            stride = 2 if (j == 0 and i > 0) else 1
            blk = {
                "c1": _init_conv(ks[next(it)], 1, 1, cin, w(mid)),
                "c2": _init_conv(ks[next(it)], 3, 3, w(mid), w(mid)),
                "c3": _init_conv(ks[next(it)], 1, 1, w(mid), w(out)),
                "stride": stride,
            }
            if j == 0:
                blk["proj"] = _init_conv(ks[next(it)], 1, 1, cin, w(out))
            blocks.append(blk)
            cin = w(out)
    p["blocks"] = blocks
    p["head"] = jax.random.normal(ks[next(it)], (cin, classes)) * 0.01
    return p


def resnet50_apply(p: Pytree, x: jax.Array, use_kernel: bool = False) -> jax.Array:
    conv = _numbered(use_kernel)
    h = jax.nn.relu(conv(x, p["stem"], 2))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for blk in p["blocks"]:
        s = blk["stride"]
        r = conv(h, blk["proj"], s) if "proj" in blk else h
        h2 = jax.nn.relu(conv(h, blk["c1"], 1))
        h2 = jax.nn.relu(conv(h2, blk["c2"], s))
        h2 = conv(h2, blk["c3"], 1)
        h = jax.nn.relu(h2 + r)
    h = jnp.mean(h, axis=(1, 2))
    return h @ p["head"]


# --------------------------------------------------------------------------
# EfficientNet-B0-style MBConv net
# --------------------------------------------------------------------------

def effnet_init(key, *, width: float = 1.0, classes: int = 1000) -> Pytree:
    ks = iter(jax.random.split(key, 128))
    w = lambda c: max(8, int(c * width))
    p = {"stem": _init_conv(next(ks), 3, 3, 3, w(32))}
    stages = [(1, 32, 16, 1), (2, 16, 24, 6), (2, 24, 40, 6), (3, 40, 80, 6),
              (1, 80, 112, 6)]
    blocks = []
    for n, cin, cout, exp in stages:
        for j in range(n):
            ci = w(cin) if j == 0 else w(cout)
            mid = ci * exp
            blocks.append({
                "expand": _init_conv(next(ks), 1, 1, ci, mid),
                "dw": jax.random.normal(next(ks), (3, 3, 1, mid)) * 0.3,
                "project": _init_conv(next(ks), 1, 1, mid, w(cout)),
                "stride": 2 if j == 0 and cin != cout and cin > 16 else 1,
            })
    p["blocks"] = blocks
    p["head_conv"] = _init_conv(next(ks), 1, 1, w(112), w(320))
    p["head"] = jax.random.normal(next(ks), (w(320), classes)) * 0.01
    return p


def effnet_apply(p, x, use_kernel: bool = False):
    conv = _numbered(use_kernel)
    h = jax.nn.silu(conv(x, p["stem"], 2))
    for blk in p["blocks"]:
        inp = h
        h2 = jax.nn.silu(conv(h, blk["expand"], 1))
        h2 = jax.nn.silu(lax.conv_general_dilated(
            h2, blk["dw"], (blk["stride"],) * 2, "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=h2.shape[-1]))
        h2 = conv(h2, blk["project"], 1)
        h = h2 + inp if h2.shape == inp.shape else h2
    h = jax.nn.silu(conv(h, p["head_conv"], 1))
    h = jnp.mean(h, axis=(1, 2))
    return h @ p["head"]


# --------------------------------------------------------------------------
# FCN (ResNet backbone + dense upsampling head)
# --------------------------------------------------------------------------

def fcn_init(key, *, width: float = 1.0, classes: int = 21) -> Pytree:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"backbone": resnet50_init(k1, width=width, classes=classes)}
    cin = max(8, int(2048 * width))
    p["score"] = _init_conv(k2, 3, 3, cin, classes)
    p["out"] = _init_conv(k3, 1, 1, classes, classes)
    return p


def fcn_apply(p, x, use_kernel: bool = False):
    conv = _numbered(use_kernel)
    bb = p["backbone"]
    h = jax.nn.relu(conv(x, bb["stem"], 2))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for blk in bb["blocks"]:
        s = blk["stride"]
        r = conv(h, blk["proj"], s) if "proj" in blk else h
        h2 = jax.nn.relu(conv(h, blk["c1"], 1))
        h2 = jax.nn.relu(conv(h2, blk["c2"], s))
        h2 = conv(h2, blk["c3"], 1)
        h = jax.nn.relu(h2 + r)
    h = conv(h, p["score"], 1)
    # bilinear-ish upsample back to input resolution
    H = x.shape[1]
    h = jax.image.resize(h, (h.shape[0], H, H, h.shape[-1]), "linear")
    return conv(h, p["out"], 1)


# --------------------------------------------------------------------------
# YOLOv3 (darknet-53 trunk + 1 detection head; width-scalable)
# --------------------------------------------------------------------------

def yolov3_init(key, *, width: float = 1.0) -> Pytree:
    ks = iter(jax.random.split(key, 128))
    w = lambda c: max(8, int(c * width))
    p = {"stem": _init_conv(next(ks), 3, 3, 3, w(32))}
    trunk = []
    cin = w(32)
    for n, cout in [(1, 64), (1, 128), (2, 256), (2, 512), (1, 1024)]:
        stage = {"down": _init_conv(next(ks), 3, 3, cin, w(cout)), "res": []}
        for _ in range(n):
            stage["res"].append((
                _init_conv(next(ks), 1, 1, w(cout), w(cout) // 2),
                _init_conv(next(ks), 3, 3, w(cout) // 2, w(cout))))
        trunk.append(stage)
        cin = w(cout)
    p["trunk"] = trunk
    p["head"] = _init_conv(next(ks), 1, 1, cin, 255)
    return p


def yolov3_apply(p, x, use_kernel: bool = False):
    conv = _numbered(use_kernel)
    act = lambda v: jax.nn.leaky_relu(v, 0.1)
    h = act(conv(x, p["stem"], 1))
    for stage in p["trunk"]:
        h = act(conv(h, stage["down"], 2))
        for c1, c2 in stage["res"]:
            r = h
            h = act(conv(h, c1, 1))
            h = act(conv(h, c2, 1))
            h = h + r
    return conv(h, p["head"], 1)


# --------------------------------------------------------------------------
# ViT-H/14 (Dosovitskiy et al. 2020, Table 1 "ViT-Huge"), width-scalable
# --------------------------------------------------------------------------

def _fan_in(key, shape):
    """N(0, 1/fan_in) weights; the fan-in is every axis but the last."""
    return jax.random.normal(key, shape) * math.prod(shape[:-1]) ** -0.5


def _small(key, shape):
    return jax.random.normal(key, shape) * 0.02


# the resolution the position table is laid out for: ViT's pre-training
# resolution, and the Table I function's 224x224 input
VIT_TABLE_IMAGE = 224


def vit_init(key, *, width: float = 1.0, layers: int = 32, d: int = 1280,
             heads: int = 16, d_ff: int = 5120, patch: int = 14,
             classes: int = 1000) -> Pytree:
    """ViT-H/14 with its width ``d`` and MLP ``d_ff`` scaled by ``width``
    and its ``heads`` kept (head dim ``d * width / heads``); the position
    table is laid out for ``VIT_TABLE_IMAGE`` pixels (257 rows at patch
    14).

    Weights from ``split(key, 8 + 12 * layers)`` taken in order: patch
    kernel and bias, class token, position table; per block ln1 scale and
    bias, qkv, its bias, out, its bias, ln2 scale and bias, fc1, its bias,
    fc2, its bias; the final norm's scale and bias; head, its bias.
    Kernels are N(0, 1/fan_in); biases, LayerNorm biases, the class token
    and the position table N(0, 0.02^2); LayerNorm scales 1 + N(0, 0.02^2).
    """
    d, d_ff = round(d * width), round(d_ff * width)
    if d % heads:
        raise ValueError(f"width {d} does not split into {heads} heads")
    ks = iter(jax.random.split(key, 8 + 12 * layers))

    def dense(k, n):
        return {"w": _fan_in(next(ks), (k, n)), "b": _small(next(ks), (n,))}

    def norm():
        return {"scale": 1.0 + _small(next(ks), (d,)),
                "bias": _small(next(ks), (d,))}
    p = {"patch": {"w": _fan_in(next(ks), (patch, patch, 3, d)),
                   "b": _small(next(ks), (d,))},
         "cls": _small(next(ks), (1, 1, d)),
         "pos": _small(next(ks), (1 + (VIT_TABLE_IMAGE // patch) ** 2, d)),
         "blocks": []}
    for _ in range(layers):
        p["blocks"].append({"ln1": norm(), "qkv": dense(d, 3 * d),
                            "out": dense(d, d), "ln2": norm(),
                            "fc1": dense(d, d_ff), "fc2": dense(d_ff, d)})
    p["norm"] = norm()
    p["head"] = dense(d, classes)
    p["meta"] = {"heads": heads, "patch": patch}
    return p


def _layer_norm(x, p, eps: float = 1e-6):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * p["scale"] + p["bias"]


def _linear(x, p, use_kernel: bool, act: str = "none"):
    """x (..., K) @ w + b, then ``act``: on the kernel path one padded
    systolic call with the bias and activation in its epilogue."""
    lead, k = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, k)
    if use_kernel:
        from repro.kernels import ops
        y = ops.matmul_padded(x2, p["w"], p["b"], act=act)
    else:
        y = x2 @ p["w"] + p["b"]
        if act == "gelu":
            y = jax.nn.gelu(y, approximate=True)
    return y.reshape(*lead, -1)


def _resize_grid(pos, gh: int, gw: int):
    """The position table for a ``gh`` x ``gw`` grid of patches: the class
    row kept, the square grid of the rest resized linearly with its
    corners aligned, as the release resizes a checkpoint's table for
    another resolution (``scipy.ndimage.zoom``, order 1).  Index and
    weight arithmetic only: no product."""
    g = math.isqrt(pos.shape[0] - 1)
    if (gh, gw) == (g, g):
        return pos
    grid = pos[1:].reshape(g, g, -1)
    for axis, n in ((0, gh), (1, gw)):
        src = np.linspace(0.0, g - 1, n)
        lo = np.floor(src).astype(np.int32)
        hi = np.minimum(lo + 1, g - 1)
        t = jnp.asarray(src - lo, pos.dtype).reshape(
            (-1, 1, 1) if axis == 0 else (1, -1, 1))
        grid = (jnp.take(grid, lo, axis=axis) * (1 - t)
                + jnp.take(grid, hi, axis=axis) * t)
    return jnp.concatenate([pos[:1], grid.reshape(gh * gw, -1)], 0)


def _self_attention(qkv, heads: int, use_kernel: bool):
    """(B, S, 3d) fused projections, columns [q | k | v] each ``heads`` x
    ``d/heads`` -> (B, S, d): non-causal softmax attention, scale
    1/sqrt(d/heads), over the whole sequence as one block."""
    B, S, d3 = qkv.shape
    d = d3 // 3
    q, k, v = qkv.reshape(B, S, 3, heads, d // heads).transpose(2, 0, 3, 1, 4)
    if use_kernel:
        from repro.kernels import ops
        o = ops.attention(q, k, v, causal=False, bq=S, bk=S)
    else:
        from repro.kernels import ref
        o = ref.attention_ref(q, k, v, causal=False)
    return o.transpose(0, 2, 1, 3).reshape(B, S, d)


def vit_apply(p, x, use_kernel: bool = False):
    """x (B, H, W, 3) image -> logits (B, classes), as the authors' release
    (``google-research/vision_transformer``, ``models_vit.py``) computes
    them: the patch embedding as a stride-``patch`` convolution with bias
    (scope ``conv0``); the class token prepended and the position table
    added (``tokens``); pre-norm blocks (``block<i>``: ``ln1``, ``qkv``,
    ``attn``, ``out``, ``ln2``, ``fc1`` with tanh GELU, ``fc2``, residual
    adds); the final LayerNorm of the class token (``norm``) and the head
    (``head``).  The fused QKV projection is the release's three."""
    patch, heads = p["meta"]["patch"], p["meta"]["heads"]
    B, H, W, _ = x.shape
    if H % patch or W % patch:
        raise ValueError(f"a {H}x{W} image does not tile into "
                         f"{patch}x{patch} patches")
    h = _numbered(use_kernel)(x, p["patch"]["w"], patch, p["patch"]["b"])
    with jax.named_scope("tokens"):
        d = h.shape[-1]
        cls = jnp.broadcast_to(p["cls"], (B, 1, d))
        h = jnp.concatenate([cls, h.reshape(B, -1, d)], 1)
        h = h + _resize_grid(p["pos"], H // patch, W // patch)
    for i, blk in enumerate(p["blocks"]):
        with jax.named_scope(f"block{i}"):
            with jax.named_scope("ln1"):
                y = _layer_norm(h, blk["ln1"])
            with jax.named_scope("qkv"):
                y = _linear(y, blk["qkv"], use_kernel)
            with jax.named_scope("attn"):
                y = _self_attention(y, heads, use_kernel)
            with jax.named_scope("out"):
                y = _linear(y, blk["out"], use_kernel)
            h = h + y
            with jax.named_scope("ln2"):
                y = _layer_norm(h, blk["ln2"])
            with jax.named_scope("fc1"):
                y = _linear(y, blk["fc1"], use_kernel, act="gelu")
            with jax.named_scope("fc2"):
                y = _linear(y, blk["fc2"], use_kernel)
            h = h + y
    # the final norm is per token, so normalising the class token alone
    # is the release's norm-then-take
    with jax.named_scope("norm"):
        y = _layer_norm(h[:, 0], p["norm"])
    with jax.named_scope("head"):
        return _linear(y, p["head"], use_kernel)
