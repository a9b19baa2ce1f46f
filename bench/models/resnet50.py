"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer column)
as the Table I ``asset_damage`` function serves it, in plain ``lax``.

Bottleneck blocks with the stride on the 3x3 convolution, a 1x1
projection on each stage's first block, no batch norm, ReLU, global mean
pool and a dense head.  Weights come from the seed by the recipe the
served function uses: ``split(PRNGKey(seed), 256)`` taken in order (stem;
per block c1, c2, c3, then the projection; head), He-normal convolutions
and a head of N(0, 0.01^2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.refops import Conv, channels, conv, init_conv, matmul, normalize

# the configuration keys, and their values, at which the CPU (Pallas in
# interpret mode) serves and checks a request in seconds
CPU_SIZE = {"width": 0.125, "image_size": 32}


def _blocks(cfg):
    """(cin, mid, out, stride, has_proj) of every bottleneck block."""
    w = cfg["width"]
    cin = channels(cfg["stem"]["channels"], w)
    for i, (n, (mid, out)) in enumerate(zip(cfg["stage_blocks"],
                                            cfg["stage_widths"])):
        for j in range(n):
            yield (cin, channels(mid, w), channels(out, w),
                   2 if (j == 0 and i > 0) else 1, j == 0)
            cin = channels(out, w)


def init(key, cfg):
    ks = iter(jax.random.split(key, 256))
    stem = cfg["stem"]
    k, c0 = stem["kernel"], channels(stem["channels"], cfg["width"])
    p = {"stem": init_conv(next(ks), k, k, cfg["in_channels"], c0), "blocks": []}
    cout = c0
    for cin, mid, cout, _, proj in _blocks(cfg):
        blk = {"c1": init_conv(next(ks), 1, 1, cin, mid),
               "c2": init_conv(next(ks), 3, 3, mid, mid),
               "c3": init_conv(next(ks), 1, 1, mid, cout)}
        if proj:
            blk["proj"] = init_conv(next(ks), 1, 1, cin, cout)
        p["blocks"].append(blk)
    p["head"] = jax.random.normal(next(ks), (cout, cfg["classes"])) * 0.01
    return p


def forward(p, frames, cfg, passes=None):
    """uint8 frames (B, H, W, 3) -> logits (B, classes)."""
    stem = cfg["stem"]
    h = jax.nn.relu(conv(normalize(frames), p["stem"], stem["stride"], passes))
    r = stem["pool"]
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, r, r, 1), (1, 2, 2, 1),
                          "SAME")
    for blk, (_, _, _, s, proj) in zip(p["blocks"], _blocks(cfg)):
        res = conv(h, blk["proj"], s, passes) if proj else h
        h2 = jax.nn.relu(conv(h, blk["c1"], 1, passes))
        h2 = jax.nn.relu(conv(h2, blk["c2"], s, passes))
        h = jax.nn.relu(conv(h2, blk["c3"], 1, passes) + res)
    return matmul(jnp.mean(h, axis=(1, 2)), p["head"], passes)


def convs(cfg):
    """Every convolution of one batch-1 forward, in program order."""
    stem = cfg["stem"]
    size = cfg["image_size"]
    c0 = channels(stem["channels"], cfg["width"])
    out = [Conv(size, size, cfg["in_channels"], c0, stem["kernel"],
                stem["stride"])]
    size = -(-size // stem["stride"])
    size = -(-size // 2)                          # max pool, stride 2
    for cin, mid, cout, s, proj in _blocks(cfg):
        if proj:
            out.append(Conv(size, size, cin, cout, 1, s))
        out.append(Conv(size, size, cin, mid, 1, 1))
        out.append(Conv(size, size, mid, mid, 3, s))
        size = -(-size // s)
        out.append(Conv(size, size, mid, cout, 1, 1))
    return out


def head_flops(cfg):
    """The dense head, which runs on XLA rather than the systolic kernel."""
    last = channels(cfg["stage_widths"][-1][1], cfg["width"])
    return 2 * last * cfg["classes"]
