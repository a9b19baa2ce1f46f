"""The paper's Table I vision models in JAX (ResNet-50, EfficientNet-B0-ish,
FCN, YOLOv3, ViT), structurally faithful with a ``width`` multiplier for
CPU-scale smoke/demo runs.

Convolutions can execute through the DSA path: im2col patches ->
``kernels.ops.matmul`` (the systolic kernel) — the paper's compiler story.
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
           use_kernel: bool = False, *, name: str) -> jax.Array:
    """x (B,H,W,C); w (kh,kw,C,O), SAME padding, in the named scope
    ``name``.  On the kernel path its steps have scopes of their own:
    ``im2col`` (:func:`im2col`'s patches as an (M, K) matrix),
    ``weights`` (``w`` as (K, O): a reshape, since the patches share the
    HWIO order) and ``gemm`` (the padded systolic call)."""
    with jax.named_scope(name):
        if not use_kernel:
            return lax.conv_general_dilated(
                x, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        kh, kw, c, o = w.shape
        with jax.named_scope("im2col"):
            patches = im2col(x, kh, kw, stride)
            B, H2, W2, K = patches.shape
            patches = patches.reshape(B * H2 * W2, K)
        from repro.kernels import ops
        with jax.named_scope("weights"):
            w2 = w.reshape(K, o)
        with jax.named_scope("gemm"):
            return ops.matmul_padded(patches, w2).reshape(B, H2, W2, o)


def _numbered(use_kernel: bool):
    """``conv2d`` for one forward pass, each call in the scope ``conv<i>``,
    ``i`` counting the calls in program order."""
    n = itertools.count()
    return lambda x, w, stride: conv2d(x, w, stride, use_kernel,
                                       name=f"conv{next(n)}")


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
# ViT encoder (patch embeddings precomputed or raw image)
# --------------------------------------------------------------------------

def vit_init(key, *, layers=4, d=128, heads=4, d_ff=256, patch=16,
             classes=1000) -> Pytree:
    ks = iter(jax.random.split(key, 8 + 8 * layers))
    p = {"patch": jax.random.normal(next(ks), (patch * patch * 3, d)) * 0.02,
         "pos": jax.random.normal(next(ks), (1024, d)) * 0.01,
         "cls": jax.random.normal(next(ks), (1, 1, d)) * 0.02,
         "head": jax.random.normal(next(ks), (d, classes)) * 0.02,
         "blocks": []}
    for _ in range(layers):
        p["blocks"].append({
            "qkv": jax.random.normal(next(ks), (d, 3 * d)) * 0.02,
            "o": jax.random.normal(next(ks), (d, d)) * 0.02,
            "w1": jax.random.normal(next(ks), (d, d_ff)) * 0.02,
            "w2": jax.random.normal(next(ks), (d_ff, d)) * 0.02,
            "ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,)),
        })
    p["meta"] = {"heads": heads, "patch": patch}
    return p


def vit_apply(p, x, use_kernel: bool = False):
    """x (B, H, W, 3) image."""
    from repro.models.layers import rms_norm
    patch = p["meta"]["patch"]
    heads = p["meta"]["heads"]
    B, H, W, C = x.shape
    xp = x.reshape(B, H // patch, patch, W // patch, patch, C)
    xp = xp.transpose(0, 1, 3, 2, 4, 5).reshape(B, -1, patch * patch * C)
    h = xp @ p["patch"] + p["pos"][None, :xp.shape[1]]
    h = jnp.concatenate([jnp.broadcast_to(p["cls"], (B, 1, h.shape[-1])), h], 1)
    d = h.shape[-1]
    hd = d // heads
    for blk in p["blocks"]:
        hn = rms_norm(h, blk["ln1"])
        qkv = hn @ blk["qkv"]
        q, k, v = jnp.split(qkv.reshape(B, -1, 3, heads, hd), 3, axis=2)
        q, k, v = (t[:, :, 0].transpose(0, 2, 1, 3) for t in (q, k, v))
        if use_kernel:
            from repro.kernels import ops
            o = ops.attention(q, k, v, causal=False,
                              bq=min(128, q.shape[2]), bk=min(128, q.shape[2]))
        else:
            from repro.kernels import ref
            o = ref.attention_ref(q, k, v, causal=False)
        o = o.transpose(0, 2, 1, 3).reshape(B, -1, d)
        h = h + o @ blk["o"]
        hn = rms_norm(h, blk["ln2"])
        h = h + jax.nn.gelu(hn @ blk["w1"]) @ blk["w2"]
    return h[:, 0] @ p["head"]
