"""The Darknet-53 trunk of YOLOv3 (Redmon & Farhadi 2018, arXiv:1804.02767,
Table 1) with one 13x13 detection head, as the Table I ``ppe_detection``
function serves it, in plain ``lax``.

A 3x3 stem at stride 1; per stage a 3x3 stride-2 down-sampling
convolution, then residual blocks of a 1x1 (halving the channels) and a
3x3 convolution; leaky ReLU (0.1) after every convolution but the head,
no batch norm; a 1x1 head with 255 outputs (3 anchors x (80 classes + 5)).
Weights come from the seed by the served function's recipe:
``split(PRNGKey(seed), 128)`` taken in order (stem; per stage the
down-sampling convolution, then each block's two), He-normal.
"""
from __future__ import annotations

import jax

from bench.refops import Conv, channels, conv, init_conv, normalize

# the configuration keys, and their values, at which the CPU (Pallas in
# interpret mode) serves and checks a request in seconds
CPU_SIZE = {"width": 0.125, "image_size": 64}


def _stages(cfg):
    w = cfg["width"]
    cin = channels(cfg["stem_channels"], w)
    for n, c in zip(cfg["stage_blocks"], cfg["stage_channels"]):
        yield cin, channels(c, w), n
        cin = channels(c, w)


def init(key, cfg):
    ks = iter(jax.random.split(key, 128))
    c0 = channels(cfg["stem_channels"], cfg["width"])
    p = {"stem": init_conv(next(ks), 3, 3, cfg["in_channels"], c0),
         "stages": []}
    cout = c0
    for cin, cout, n in _stages(cfg):
        p["stages"].append({
            "down": init_conv(next(ks), 3, 3, cin, cout),
            "res": [(init_conv(next(ks), 1, 1, cout, cout // 2),
                     init_conv(next(ks), 3, 3, cout // 2, cout))
                    for _ in range(n)]})
    p["head"] = init_conv(next(ks), 1, 1, cout, cfg["head_channels"])
    return p


def forward(p, frames, cfg, passes=None):
    """uint8 frames (B, H, W, 3) -> head output (B, H/32, W/32, 255)."""
    def act(v):
        return jax.nn.leaky_relu(v, 0.1)
    h = act(conv(normalize(frames), p["stem"], 1, passes))
    for st in p["stages"]:
        h = act(conv(h, st["down"], 2, passes))
        for c1, c2 in st["res"]:
            r = h
            h = act(conv(h, c1, 1, passes))
            h = act(conv(h, c2, 1, passes)) + r
    return conv(h, p["head"], 1, passes)


def convs(cfg):
    size = cfg["image_size"]
    c0 = channels(cfg["stem_channels"], cfg["width"])
    out = [Conv(size, size, cfg["in_channels"], c0, 3, 1)]
    cout = c0
    for cin, cout, n in _stages(cfg):
        out.append(Conv(size, size, cin, cout, 3, 2))
        size = -(-size // 2)
        for _ in range(n):
            out.append(Conv(size, size, cout, cout // 2, 1, 1))
            out.append(Conv(size, size, cout // 2, cout, 3, 1))
    out.append(Conv(size, size, cout, cfg["head_channels"], 1, 1))
    return out


def head_flops(cfg):
    return 0          # the head is a 1x1 convolution, counted in convs()
