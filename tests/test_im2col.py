"""The kernel path's im2col: patches built by padding and strided slicing
alone, so a convolution on the DSA path is the systolic kernel's GEMM and
nothing else.

Covers the served shapes at reduced spatial size (the 7x7 stride-2 stem on
three channels, 3x3 at stride 1 and 2 with odd outputs, 1x1 at both
strides), the patches against ``lax.conv_general_dilated_patches`` bit for
bit, and the served f1+f2 programs of the benchmark's configurations,
lowered (not compiled), for any convolution left on the kernel path.
"""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.models import vision

ROOT = Path(__file__).resolve().parents[1]

# (H = W, C, kernel, stride, O)
SHAPES = {
    "stem7x7s2": (32, 3, 7, 2, 16),
    "3x3s1odd": (13, 16, 3, 1, 8),
    "3x3s2odd": (13, 16, 3, 2, 8),
    "3x3s2even": (14, 16, 3, 2, 8),
    "1x1s1": (9, 16, 1, 1, 8),
    "1x1s2": (9, 16, 1, 2, 8),
}


def _inputs(H, C, k, O):
    kx, kw = jax.random.split(jax.random.PRNGKey(H * 100 + C + k))
    return (jax.random.normal(kx, (1, H, H, C)),
            jax.random.normal(kw, (k, k, C, O)) / np.sqrt(k * k * C))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_conv2d_matches_xla_convolution(shape):
    H, C, k, s, O = SHAPES[shape]
    x, w = _inputs(H, C, k, O)
    got = vision.conv2d(x, w, s, use_kernel=True, name="c")
    want = lax.conv_general_dilated(
        x, w, (s, s), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    assert got.shape == want.shape == (1, -(-H // s), -(-H // s), O)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_patches_are_xla_patches_in_hwio_order(shape):
    """The same values as XLA's patches, reordered from (C, kh, kw) to
    (kh, kw, C) along the last axis: exact, since both only copy."""
    H, C, k, s, _ = SHAPES[shape]
    x, _ = _inputs(H, C, k, 1)
    got = vision.im2col(x, k, k, s)
    ref = lax.conv_general_dilated_patches(
        x, (k, k), (s, s), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    B, H2, W2, K = ref.shape
    ref = ref.reshape(B, H2, W2, C, k, k).transpose(0, 1, 2, 4, 5, 3)
    np.testing.assert_array_equal(got, ref.reshape(B, H2, W2, K))


@pytest.mark.parametrize("config", ["resnet50-asset_damage",
                                    "yolov3-ppe_detection"])
def test_served_program_has_no_convolution(config):
    """Every convolution of the served f1+f2 program runs as im2col copies
    and the systolic kernel: its lowering holds no convolution op."""
    from repro.core.executor import DSCSExecutor
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    ex = DSCSExecutor(cfg["pipeline"], image_size=cfg["image_size"],
                      width=cfg["width"])
    s = cfg["image_size"]
    text = ex.lower(jnp.zeros((1, s, s, cfg["in_channels"]),
                              jnp.uint8)).as_text()
    n_convs = sum(a.ndim == 4 for a in ex._arrays)
    assert len(re.findall(r"call @systolic_matmul", text)) == n_convs
    assert "stablehlo.convolution" not in text
