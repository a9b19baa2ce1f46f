"""Pure-jnp oracles for every Pallas kernel (shape/dtype-sweep targets);
the Lindley oracle is float64 numpy."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.kernels.systolic_matmul import _ACTS


def matmul_ref(x, w, b=None, *, act: str = "none", out_dtype=None):
    acc = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    if b is not None:
        acc = acc + b.astype(jnp.float32)
    return _ACTS[act](acc).astype(out_dtype or x.dtype)


def attention_ref(q, k, v, *, causal=True, window=0):
    """q (B,H,Sq,D); k/v (B,KV,Skv,D) — dense masked softmax."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Sq, D).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32))
    s = s / math.sqrt(D)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", w, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, D).astype(q.dtype)


def affine_act_ref(x, scale, bias, *, act="none", out_dtype=None):
    y = x.astype(jnp.float32) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return _ACTS[act](y).astype(out_dtype or x.dtype)


def quantize_int8_ref(x):
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8_ref(q, scale, *, out_dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(out_dtype)


def rglru_ref(x, gx, ga, log_a, h0):
    """Associative-scan RG-LRU (models.layers.rglru)."""
    seq, _ = L.rglru(x, gx, ga, log_a, h0)
    return seq


def ssd_ref(x, dt, A, Bm, Cm, *, chunk):
    """Chunked SSD via associative scan (models.layers.ssd_chunked)."""
    return L.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)


def lindley_ref(t, s):
    """Batched FCFS Lindley starts: t/s (R, W) -> start (R, W), in
    float64 numpy (the precision the simulator's host backends keep)."""
    t = np.asarray(t, dtype=np.float64)
    s = np.asarray(s, dtype=np.float64)
    prev = np.cumsum(s, axis=1) - s
    m = np.maximum.accumulate(t - prev, axis=1)
    return np.maximum(t, m + prev)
