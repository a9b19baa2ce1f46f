"""ViT-H/14, the Table I ``remote_sensing`` function: the served kernel
path against the benchmark's plain reference (``bench/models/vit_h14.py``)
on seeded weights at a small width, every bias and LayerNorm parameter
exercised by that comparison, the published counts, and the per-layer
metrics that read the program's attention and dense-layer scopes."""
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, scopes                            # noqa: E402
from bench import trace as tr                            # noqa: E402
from repro.core.executor import DSCSExecutor             # noqa: E402
from repro.models import vision                          # noqa: E402

MODEL = run.load_module(ROOT, "models", "vit_h14")
CFG = json.loads((ROOT / "bench/configs/vit_h14-remote_sensing.json")
                 .read_text())
SEED = 2**31 + 21


def _cpu_cfg():
    return {**CFG, **MODEL.CPU_SIZE}


@pytest.fixture(scope="module")
def served():
    """The kernel path at ``CPU_SIZE`` (5 tokens, 16 heads of 8), the
    reference's weights from the same seed, and four frames."""
    cfg = _cpu_cfg()
    with jax.default_matmul_precision("float32"):
        ex = DSCSExecutor("remote_sensing", width=cfg["width"],
                          image_size=cfg["image_size"], seed=SEED)
        ref_params = MODEL.init(jax.random.PRNGKey(SEED), cfg)
        s = cfg["image_size"]
        frames = jax.random.randint(jax.random.PRNGKey(SEED), (4, 1, s, s, 3),
                                    0, 256).astype(jnp.uint8)
        refs = [np.asarray(MODEL.forward(ref_params, f, cfg)) for f in frames]
    return ex, frames, refs


def _worst_gap(ex, arrays, frames, refs):
    with jax.default_matmul_precision("float32"):
        outs = [np.asarray(ex._infer(arrays, f)) for f in frames]
    return max(run.gap(o, o.argmax(-1), r) for o, r in zip(outs, refs))


def test_kernel_path_matches_the_reference(served):
    """The served program (f1 on the vector engine, every GEMM on the
    systolic kernel, attention on the flash kernel) agrees with the
    reference within the cell's limit."""
    ex, frames, refs = served
    text = ex.lower(frames[0]).as_text()
    # per block qkv, out, fc1, fc2; the patch embedding and the head
    assert len(re.findall(r"call @systolic_matmul", text)) == 32 * 4 + 2
    assert len(re.findall(r"call @flash_attention", text)) == 32
    assert len(re.findall(r"call @fused_affine_act", text)) == 1
    assert "stablehlo.convolution" not in text
    gap = _worst_gap(ex, ex._arrays, frames, refs)
    assert gap < CFG["check"]["worst_gap_limit"], gap


def _leaf_paths(params):
    """The key path of each array leaf, in the executor's array order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, v in flat if isinstance(v, jax.Array)]


@pytest.mark.parametrize("leaf,value", [
    ("patch/b", 0.0), ("blocks/0/qkv/b", 0.0), ("blocks/31/out/b", 0.0),
    ("blocks/7/fc1/b", 0.0), ("blocks/7/fc2/b", 0.0),
    ("blocks/0/ln1/bias", 0.0), ("blocks/31/ln2/scale", 1.0),
    ("norm/bias", 0.0), ("head/b", 0.0)])
def test_each_bias_and_norm_parameter_is_exercised(served, leaf, value):
    """Set one bias to zero, or one LayerNorm scale to one, and the same
    comparison fails: each is in the served path and in the reference."""
    ex, frames, refs = served
    i = _leaf_paths(ex.params).index(leaf)
    arrays = list(ex._arrays)
    arrays[i] = jnp.full_like(arrays[i], value)
    gap = _worst_gap(ex, arrays, frames, refs)
    assert gap > CFG["check"]["worst_gap_limit"], (leaf, gap)


def _count(tree):
    return sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree)
               if hasattr(v, "shape"))


def test_published_parameter_and_flop_counts():
    """ViT-H/14 at 224 px: 632,045,800 parameters in the served model and
    in the reference; 334,590,218,240 FLOP a request (the patch
    embedding, 32 blocks of four GEMMs and two attention products over
    257 tokens, the head).  Shapes only: no weight is made."""
    key = jax.random.PRNGKey(0)
    served = jax.eval_shape(
        lambda k: {n: v for n, v in vision.vit_init(k).items()
                   if n != "meta"}, key)
    assert _count(served) == 632_045_800 == CFG["parameters"]
    assert _count(jax.eval_shape(lambda k: MODEL.init(k, CFG), key)) \
        == 632_045_800
    flops = sum(c.flops for c in MODEL.convs(CFG)) + MODEL.head_flops(CFG)
    assert flops == 334_590_218_240
    assert MODEL.convs(CFG)[0].m == 256


def test_least_times_at_the_v5e_peaks():
    """The least times the roofline metrics divide by: the block GEMMs
    bound by their float32 bytes, 3.90 ms a request; attention 0.21 ms."""
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())["TPU v5 lite"]
    dense = MODEL.least_seconds(MODEL.dense_work(CFG), peaks)
    attn = MODEL.least_seconds(MODEL.attention_work(CFG), peaks)
    assert dense == pytest.approx(3.8954e-3, rel=1e-4)
    assert attn == pytest.approx(0.2057e-3, rel=1e-3)
    assert all(b / peaks["hbm_bytes_per_s"] > fl / peaks["bf16_flops_per_s"]
               for fl, b in MODEL.dense_work(CFG))


def test_image_that_does_not_tile_into_patches_is_refused():
    params = vision.vit_init(jax.random.PRNGKey(0), width=0.1, layers=1)
    with pytest.raises(ValueError, match="does not tile into 14x14"):
        vision.vit_apply(params, jnp.zeros((1, 30, 28, 3)))


def test_position_table_resized_to_the_grid_keeps_the_corners():
    """At another resolution the 16x16 grid of the table is resized with
    its corners aligned (the release's order-1 zoom), served and
    reference alike; at the table's own grid it is used as it is."""
    pos = jax.random.normal(jax.random.PRNGKey(1), (1 + 16 * 16, 8))
    assert vision._resize_grid(pos, 16, 16) is pos
    for n in (1, 2, 3, 7):
        got = vision._resize_grid(pos, n, n)
        want = MODEL._posemb(pos, n)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert got.shape == (1 + n * n, 8)
    grid = np.asarray(pos[1:]).reshape(16, 16, 8)
    two = np.asarray(vision._resize_grid(pos, 2, 2))[1:].reshape(2, 2, 8)
    np.testing.assert_array_equal(two, grid[::15, ::15])


def test_metrics_read_the_attention_and_dense_scopes(served, monkeypatch):
    """A synthetic trace of the compiled CPU-size program, one op per
    instruction of 1 us each: ``attention_ms`` counts the ops of the
    ``attn`` scopes, both rooflines divide their least time by the ops
    of their scopes."""
    ex, frames, _ = served
    text = scopes.program_text(_cpu_cfg(), ex)
    monkeypatch.setattr(scopes, "program_text", lambda cfg, e=None: text)
    smap = scopes.scope_map(text)
    names = sorted(smap)
    n = len(names)
    t = tr.Trace(names, [None] * n, np.zeros(n, dtype=int),
                 np.arange(n) * 1e3, np.arange(n) * 1e3 + 1e3, [],
                 (0.0, n * 1e3), 1)
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())["TPU v5 lite"]
    data = SimpleNamespace(cfg=_cpu_cfg(), trace=t, peaks=peaks, requests=2)

    def count(pattern):
        return sum(bool(re.match(pattern, smap[k])) for k in names)
    n_attn, n_dense = count(MODEL.ATTENTION_SCOPE), count(MODEL.DENSE_SCOPE)
    assert n_attn > 0 and n_dense > 0
    read = {m: run.load_module(ROOT, "metrics", m).read(data)
            for m in ("attention_ms", "attention_roofline", "dense_roofline")}
    assert read["attention_ms"] == pytest.approx(n_attn * 1e-3 / 2)
    cfg = _cpu_cfg()
    assert read["attention_roofline"] == pytest.approx(
        100 * MODEL.least_seconds(MODEL.attention_work(cfg), peaks) * 2
        / (n_attn * 1e-6))
    assert read["dense_roofline"] == pytest.approx(
        100 * MODEL.least_seconds(MODEL.dense_work(cfg), peaks) * 2
        / (n_dense * 1e-6))


def test_metrics_are_silent_for_a_model_with_no_such_scopes():
    """A convolutional configuration's model file names no attention or
    dense-layer scope: the readers return nothing and do not raise."""
    cfg = json.loads((ROOT / "bench/configs/resnet50-asset_damage.json")
                     .read_text())
    data = SimpleNamespace(cfg=cfg, trace=None, peaks={}, requests=1)
    for m in ("attention_ms", "attention_roofline", "dense_roofline"):
        assert run.load_module(ROOT, "metrics", m).read(data) is None
