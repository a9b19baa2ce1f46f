"""The benchmark's yardstick on the CPU at small widths: its operation
counter agrees with the served model's convolutions, its plain
references agree with the served model's XLA path, and its control
(three bfloat16 passes) reads above the configuration's limit.  Every
configuration of ``BENCHMARK.json`` is checked, against the program that
the executor serves for its pipeline, with no test naming a model."""
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, config, make_tiny_root, spec, write_json

from bench import run
from bench.refops import normalize

CONFIGS = [c["name"] for c in spec()["configs"]]


def _program(cfg):
    """The served model's init and apply, from the table the executor
    serves a pipeline by."""
    from repro.core.executor import _MODEL_BUILDERS
    init, apply, _ = _MODEL_BUILDERS[cfg["pipeline"]]
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


# (width, image size) as multiples of the model file's CPU_SIZE: the
# second has odd feature maps after the strides (ceil division)
SCALES = {"cpu": (1, 1), "wider": (2, 1.5)}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("scale", sorted(SCALES))
def test_flop_counter_matches_the_served_models_convolutions(name, scale):
    cpu = config(name, cpu=True)
    w, s = SCALES[scale]
    size = int(cpu["image_size"] * s)
    cfg = {**cpu, "width": cpu["width"] * w, "image_size": size}
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
    published = {"resnet50-asset_damage": 8.178e9,
                 "yolov3-ppe_detection": 20.766e9}
    for name, want in published.items():
        cfg = config(name)
        model = run.load_module(ROOT, "models", cfg["model"])
        total = sum(c.flops for c in model.convs(cfg)) + model.head_flops(cfg)
        assert abs(total - want) < 1e6, (name, total)


def _frames(cfg, n, seed):
    s = cfg["image_size"]
    return jax.random.randint(jax.random.PRNGKey(seed), (n, 1, s, s, 3),
                              0, 256).astype(jnp.uint8)


def _served_and_control_gaps(cfg, root=ROOT):
    """The widest gap of the served model's XLA path, and of the control,
    from the reference over four frames, with the same weights from one
    seed."""
    model = run.load_module(root, "models", cfg["model"])
    init, apply = _program(cfg)
    seed = 2**31 + 9
    key = jax.random.PRNGKey(seed)
    ours, theirs = model.init(key, cfg), init(key)
    count = lambda t: sum(v.size for v in jax.tree.leaves(t)
                          if isinstance(v, jax.Array))
    assert count(ours) == count(theirs)
    served, control = 0.0, 0.0
    with jax.default_matmul_precision("float32"):
        for f in _frames(cfg, 4, seed):
            ref = np.asarray(model.forward(ours, f, cfg))
            out = np.asarray(apply(theirs, normalize(f), use_kernel=False))
            low = np.asarray(model.forward(ours, f, cfg, passes=3))
            served = max(served, run.gap(out, out.argmax(-1), ref))
            control = max(control, run.gap(low, low.argmax(-1), ref))
    return served, control


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_served_xla_path_and_control_fails(name):
    """Same seed, same weights: the reference agrees with the served
    model's XLA path far inside the limit, and the control reads above
    it on the same frames."""
    cfg = config(name, cpu=True)
    served, control = _served_and_control_gaps(cfg)
    limit = cfg["check"]["worst_gap_limit"]
    assert served < limit < control, (served, limit, control)


def test_new_model_file_is_taken_with_no_other_edit(tmp_path):
    """A configuration that brings a model file of its own (here a copy
    of ResNet-50's under another name, with its own ``CPU_SIZE``), added
    as files and ``BENCHMARK.json`` entries alone: the CPU-size fixture
    cuts it by its own ``CPU_SIZE``, ``bench.run`` serves and checks a
    cell of it, and the reference-against-program check takes it."""
    src = tmp_path / "src"
    shutil.copytree(ROOT / "bench", src / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    size = {"width": 0.125, "image_size": 48}
    (src / "bench/models/resnet50_copy.py").write_text(
        (ROOT / "bench/models/resnet50.py").read_text()
        + f"\nCPU_SIZE = {size!r}\n")
    cfg = {**config("resnet50-asset_damage"), "name": "copy-asset_damage",
           "model": "resnet50_copy"}
    write_json(src / "bench/configs/copy-asset_damage.json", cfg)
    bench = spec()
    bench["configs"].append({"name": "copy-asset_damage", "source": "test",
                             "file": "bench/configs/copy-asset_damage.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "copy-c1", "config": "copy-asset_damage",
                               "traffic": "closed1", "chips": 1, "why": "test"})
    write_json(src / "BENCHMARK.json", bench)

    root = make_tiny_root(src, tmp_path / "tiny")
    tiny = config("copy-asset_damage", root)
    assert {k: tiny[k] for k in size} == size
    res = run.run_cell(run.load_cell(root, "copy-c1"), 2**31 + 11, 0.3, False,
                       require_accelerator=False, t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    served, control = _served_and_control_gaps(tiny, root)
    limit = tiny["check"]["worst_gap_limit"]
    assert served < limit < control, (served, limit, control)


def test_gap_reads_answers_and_outputs():
    ref = np.array([[1.0, 3.0, -2.0]])
    assert run.gap(ref, np.array([1]), ref) == 0.0
    assert run.gap(ref, np.array([0]), ref) == pytest.approx(2.0 / 3.0)
    assert run.gap(ref + [[0, 0, 0.3]], np.array([1]), ref) == pytest.approx(0.1)
    assert run.gap(ref * np.nan, np.array([1]), ref) == float("inf")
    assert run.gap(ref[:, :2], np.array([1]), ref) == float("inf")
