"""The benchmark's yardstick on the CPU at small widths: its operation
counter agrees with the served model's convolutions, its plain
references agree with the served model's XLA path, and its control
(three bfloat16 passes) reads above the configuration's limit."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, TINY

from bench import run
from bench.refops import normalize

CONFIGS = ["resnet50-asset_damage", "yolov3-ppe_detection"]
PROGRAM = {"resnet50": ("resnet50_init", "resnet50_apply"),
           "yolov3": ("yolov3_init", "yolov3_apply")}


def _cfg(name, **kw):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(kw)
    return cfg


def _program(cfg):
    from repro.models import vision
    init, apply = (getattr(vision, f) for f in PROGRAM[cfg["model"]])
    return (lambda key: init(key, width=cfg["width"])), apply


def _jaxpr_flops(jaxpr):
    """FLOPs of the convolutions and matmuls of a jaxpr, in order."""
    convs, dots = [], []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            c, d = _jaxpr_flops(sub)
            convs += c
            dots += d
        if eqn.primitive.name == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            kh, kw, cin, _ = eqn.invars[1].aval.shape
            convs.append(2 * int(np.prod(out)) * kh * kw * cin)
        elif eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = int(np.prod([eqn.invars[0].aval.shape[i] for i in lc]))
            dots.append(2 * int(np.prod(eqn.outvars[0].aval.shape)) * k)
    return convs, dots


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("width,size", [(0.125, 64), (0.25, 96)])
def test_flop_counter_matches_the_served_models_convolutions(name, width, size):
    cfg = _cfg(name, width=width, image_size=size)
    model = run.load_module(ROOT, "models", cfg["model"])
    init, apply = _program(cfg)
    params = init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, size, size, cfg["in_channels"]), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda v: apply(params, v, use_kernel=False))(x)
    convs, dots = _jaxpr_flops(jaxpr.jaxpr)
    assert convs == [c.flops for c in model.convs(cfg)]
    assert sum(dots) == model.head_flops(cfg)


def test_flop_counter_at_published_sizes():
    """The published forward passes: 8.18 GFLOP for ResNet-50 at 224,
    20.77 GFLOP for the served YOLOv3 trunk at 416."""
    for name, want in zip(CONFIGS, (8.178e9, 20.766e9)):
        cfg = _cfg(name)
        model = run.load_module(ROOT, "models", cfg["model"])
        total = sum(c.flops for c in model.convs(cfg)) + model.head_flops(cfg)
        assert abs(total - want) < 1e6, (name, total)


def _frames(cfg, n, seed):
    s = cfg["image_size"]
    return jax.random.randint(jax.random.PRNGKey(seed), (n, 1, s, s, 3),
                              0, 256).astype(jnp.uint8)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_served_xla_path_and_control_fails(name):
    """Same seed, same weights: the reference agrees with the served
    model's XLA path far inside the limit, and the control reads above
    it on the same frames."""
    cfg = _cfg(name, **TINY[_cfg(name)["model"]])
    model = run.load_module(ROOT, "models", cfg["model"])
    init, apply = _program(cfg)
    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    ours, theirs = model.init(key, cfg), init(key)
    count = lambda t: sum(v.size for v in jax.tree.leaves(t)
                          if isinstance(v, jax.Array))
    assert count(ours) == count(theirs)
    limit = cfg["check"]["worst_gap_limit"]
    served, control = 0.0, 0.0
    with jax.default_matmul_precision("float32"):
        for f in _frames(cfg, 4, seed):
            ref = np.asarray(model.forward(ours, f, cfg))
            out = np.asarray(apply(theirs, normalize(f), use_kernel=False))
            low = np.asarray(model.forward(ours, f, cfg, passes=3))
            served = max(served, run.gap(out, out.argmax(-1), ref))
            control = max(control, run.gap(low, low.argmax(-1), ref))
    assert served < limit < control, (served, limit, control)


def test_gap_reads_answers_and_outputs():
    ref = np.array([[1.0, 3.0, -2.0]])
    assert run.gap(ref, np.array([1]), ref) == 0.0
    assert run.gap(ref, np.array([0]), ref) == pytest.approx(2.0 / 3.0)
    assert run.gap(ref + [[0, 0, 0.3]], np.array([1]), ref) == pytest.approx(0.1)
    assert run.gap(ref * np.nan, np.array([1]), ref) == float("inf")
    assert run.gap(ref[:, :2], np.array([1]), ref) == float("inf")
