"""Named scopes and host spans inside the served function, and their
reduction: the scope map read from a compiled program's text, labels of
idle gaps by the innermost host span, the per-layer metrics that read
scopes, and the existing metrics pinned on the first recorded trace."""
import contextlib
import gzip
import json
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, config, spec

from bench import scopes
from bench import trace as tr
from bench.run import load_module

DATA = ROOT / "tests" / "bench" / "data"
CONFIGS = [c["name"] for c in spec()["configs"]]


SYNTHETIC_HLO = """\
HloModule jit_infer, is_scheduled=true

%fused_computation.1 (param_0: f32[4,4]) -> f32[4,4] {
  %param_0 = f32[4,4]{1,0} parameter(0)
  %exponential.1 = f32[4,4]{1,0} exponential(%param_0), metadata={op_name="jit(infer)/f2/conv0/im2col/exp"}
  ROOT %add.1 = f32[4,4]{1,0} add(%exponential.1, %exponential.1), metadata={op_name="jit(infer)/f2/conv1/gemm/jit(_pad)/add"}
}

%fused_computation.2 (param_0.1: f32[4,4]) -> f32[4,4] {
  %param_0.1 = f32[4,4]{1,0} parameter(0)
  ROOT %negate.1 = f32[4,4]{1,0} negate(%param_0.1)
}

ENTRY %main.12 (arrays_0_.1: f32[4,4], request.1: u8[4,4]) -> f32[4,4] {
  %arrays_0_.1 = f32[4,4]{1,0} parameter(0), metadata={op_name="arrays[0]"}
  %request.1 = u8[4,4]{1,0} parameter(1), metadata={op_name="request"}
  %convert.2 = f32[4,4]{1,0} convert(%request.1), metadata={op_name="jit(infer)/f1/convert_element_type"}
  %copy.3 = f32[4,4]{0,1:T(8,128)} copy(%arrays_0_.1), metadata={op_name="arrays[0]"}
  %fusion.4 = f32[4,4]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(infer)/f2/conv0/im2col/exp"}
  %systolic_matmul.5 = f32[4,4]{1,0} custom-call(%fusion.4, %convert.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(infer)/f2/conv1/gemm/jit(systolic_matmul)/systolic_matmul/pallas_call"}
  %maximum.6 = f32[4,4]{1,0} maximum(%systolic_matmul.5, %systolic_matmul.5), metadata={op_name="jit(infer)/f2/jit(relu)/max"}
  %fusion.7 = f32[4,4]{1,0} fusion(%maximum.6), kind=kLoop, calls=%fused_computation.2
  %copy.8 = f32[4,4]{0,1} copy(%fusion.7)
  %reshape.9 = f32[4,4]{1,0} reshape(%copy.8), metadata={op_name="jit(infer)/f2/conv2/im2col/reshape"}
  %transpose.10 = f32[4,4]{0,1} transpose(%reshape.9), dimensions={1,0}, metadata={op_name="jit(infer)/f2/conv2/weights/reshape;jit(infer)/f2/conv2/weights/transpose"}
  ROOT %copy.11 = f32[4,4]{1,0} copy(%transpose.10)
}
"""


def test_scope_of_keeps_the_named_scopes_only():
    assert scopes.scope_of("jit(infer)/f2/conv3/gemm/jit(_pad)/pad") == \
        "f2/conv3/gemm"
    assert scopes.scope_of("jit(infer)/f1/jit(fused_affine_act)/"
                           "fused_affine_act/pallas_call") == "f1"
    assert scopes.scope_of("jit(infer)/f2/add") == "f2"
    assert scopes.scope_of("jit(infer)/add") == ""
    assert scopes.scope_of("arrays[4]") == ""
    assert scopes.scope_of("arrays[4];jit(infer)/f2/conv1/weights/"
                           "transpose") == "f2/conv1/weights"


def test_scope_map_gives_fusions_their_roots_scope():
    smap = scopes.scope_map(SYNTHETIC_HLO)
    # keyed as the trace's reduction names a device op
    assert smap["fusion.4 = f32[4,4] fusion"] == "f2/conv1/gemm"
    assert smap["convert.2 = f32[4,4] convert"] == "f1"
    assert smap["systolic_matmul.5 = f32[4,4] custom-call"] == "f2/conv1/gemm"
    assert smap["maximum.6 = f32[4,4] maximum"] == "f2"
    assert smap["transpose.10 = f32[4,4] transpose"] == "f2/conv2/weights"
    # only the entry computation's instructions run as device ops
    assert "add.1 = f32[4,4] add" not in smap


def test_scope_map_gives_ops_with_no_scope_their_consumers():
    """XLA's layout copies carry no scope: a parameter's copy takes its
    user's; a chain of them before a convolution takes the convolution's
    (not the previous layer's, which it reads); an output nobody scoped
    reads takes its operand's."""
    smap = scopes.scope_map(SYNTHETIC_HLO)
    assert smap["copy.3 = f32[4,4] copy"] == "f2/conv1/gemm"
    assert smap["copy.8 = f32[4,4] copy"] == "f2/conv2/im2col"
    assert smap["fusion.7 = f32[4,4] fusion"] == "f2/conv2/im2col"
    assert smap["copy.11 = f32[4,4] copy"] == "f2/conv2/weights"
    own = scopes.scope_map(SYNTHETIC_HLO, neighbours=False)
    assert own["copy.8 = f32[4,4] copy"] == own["copy.11 = f32[4,4] copy"] \
        == ""


def test_scope_seconds_split_a_synthetic_trace():
    smap = scopes.scope_map(SYNTHETIC_HLO)
    names = ["convert.2 = f32[4,4] convert", "fusion.4 = f32[4,4] fusion",
             "systolic_matmul.5 = f32[4,4] custom-call",
             "maximum.6 = f32[4,4] maximum", "reduce.1 = s32[1] reduce"]
    t = tr.Trace(names, [None] * 5, np.zeros(5, dtype=int),
                 np.array([0.0, 10.0, 20.0, 40.0, 50.0]),
                 np.array([10.0, 20.0, 40.0, 50.0, 51.0]), [], (0.0, 60.0), 1)
    by, unscoped, outside = scopes.scope_seconds(t, smap)
    assert by == pytest.approx({"f1": 10e-9, "f2/conv1/gemm": 30e-9,
                                "f2": 10e-9})
    assert unscoped == 0.0 and outside == pytest.approx(1e-9)
    assert scopes.conv_seconds(by) == pytest.approx(30e-9)


def test_innermost_labels_keep_todays_labels_where_no_span_nests():
    spans = [("wait", 10.0, 30.0), ("invoke", 0.0, 10.0), ("fetch", 31.0, 35.0)]
    gaps = np.array([[8.0, 14.0], [2.0, 9.0], [40.0, 50.0], [29.0, 36.0],
                     [30.0, 31.0]])
    assert scopes.innermost_labels(spans, gaps) == tr.labels(spans, gaps)
    rng = np.random.default_rng(7)
    starts = np.cumsum(rng.uniform(1, 5, 200))
    spans = [(f"s{i % 4}", a, a + rng.uniform(0.5, 6))
             for i, a in enumerate(starts)]
    spans = [sp for sp in spans if not any(
        q is not sp and q[1] <= sp[1] and sp[2] <= q[2] for q in spans)]
    a = np.sort(rng.uniform(0, starts[-1], 300))
    gaps = np.stack([a, a + rng.uniform(0.1, 4, 300)], axis=1)
    assert scopes.innermost_labels(spans, gaps) == tr.labels(spans, gaps)


def test_innermost_labels_put_a_gap_in_invoke_down_to_the_span_inside():
    spans = [("pick", -1.0, 0.0), ("invoke", 0.0, 10.0), ("f1f2", 1.0, 3.0),
             ("f3", 3.0, 4.0), ("account", 4.0, 9.0), ("wait", 10.0, 30.0)]
    gaps = np.array([[2.0, 3.5],      # mostly f1f2
                     [5.0, 8.0],      # inside account
                     [8.5, 10.4],     # invoke, past its inner spans
                     [0.2, 1.5],      # invoke before f1f2 more than f1f2
                     [8.5, 20.0],     # wait
                     [40.0, 41.0]])   # no span
    assert scopes.innermost_labels(spans, gaps) == [
        "f1f2", "account", "invoke", "invoke", "wait", "other"]
    assert tr.labels(spans, gaps)[:2] == ["invoke", "invoke"]


def test_host_ms_per_request_inside_the_window():
    spans = [("f1f2", 0, 2_000_000, 1), ("f3", 2_000_000, 2_500_000, 1),
             ("account", 2_500_000, 3_500_000, 1),
             ("f1f2", 10_000_000, 12_000_000, 2),
             ("f3", 12_000_000, 12_500_000, 2),
             ("account", 12_500_000, 13_500_000, 2),
             ("account", 50_000_000, 60_000_000, 3)]      # after the window
    ms = scopes.host_ms(spans, (0, 20_000_000), 2)
    assert ms["dispatch_host_ms"] == pytest.approx(2.5)
    assert ms["account_host_ms"] == pytest.approx(1.0)


def test_executor_writes_its_spans_with_the_call_number(tmp_path):
    from repro.core.executor import DSCSExecutor
    ex = DSCSExecutor("credit_risk")
    req = ex.make_request(jax.random.PRNGKey(0))
    jax.block_until_ready(ex(req).result)
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        jax.block_until_ready(ex(req).result)
    jax.profiler.stop_trace()
    spans = scopes.host_spans(tmp_path)
    by_call = {}
    for name, a, b, call in spans:
        by_call.setdefault(call, []).append((a, b, name))
    assert sorted(by_call) == [2, 3, 4]
    for call, sps in by_call.items():
        assert [n for _, _, n in sorted(sps)] == ["f1f2", "f3", "account"]
        assert all(b >= a for a, b, _ in sps)


@pytest.mark.parametrize("name", CONFIGS)
def test_compiled_program_scopes_every_convolution(name):
    """Every convolution of the reference's count has its ``im2col``,
    ``weights`` and ``gemm`` scopes in the f1+f2 program, f1 has its own,
    and every instruction of the compiled program maps to a scope."""
    from repro.core.executor import DSCSExecutor
    cfg = config(name, cpu=True)
    n = len(load_module(ROOT, "models", cfg["model"]).convs(cfg))
    ex = DSCSExecutor(cfg["pipeline"], image_size=cfg["image_size"],
                      width=cfg["width"])
    s = cfg["image_size"]
    lowered = ex.lower(jnp.zeros((1, s, s, 3), jnp.uint8)).as_text(
        debug_info=True)
    found = set(re.findall(r'"jit\(infer\)/f2/conv(\d+)/'
                           r'(im2col|weights|gemm)/', lowered))
    assert found == {(str(i), sub) for i in range(n)
                     for sub in ("im2col", "weights", "gemm")}
    assert '"jit(infer)/f1/' in lowered
    smap = scopes.scope_map(scopes.program_text(cfg, ex))
    assert all(smap.values())
    assert {v.split("/")[1] for v in smap.values() if scopes.CONV.match(v)} \
        == {f"conv{i}" for i in range(n)}
    assert "f1" in smap.values()


@pytest.mark.parametrize("name", CONFIGS)
def test_scopes_leave_the_jaxpr_unchanged(name, monkeypatch):
    from repro.core.executor import DSCSExecutor
    cfg = config(name, cpu=True)
    ex = DSCSExecutor(cfg["pipeline"], image_size=cfg["image_size"],
                      width=cfg["width"])
    s = cfg["image_size"]
    frame = jnp.zeros((1, s, s, 3), jnp.uint8)
    infer = ex._infer.__wrapped__
    scoped = jax.make_jaxpr(infer)(ex._arrays, frame)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = jax.make_jaxpr(infer)(ex._arrays, frame)
    assert len(scoped.eqns) == len(plain.eqns)
    assert str(scoped) == str(plain)


def _run_from_trace(t, cfg):
    """What ``bench.run`` hands the metrics, rebuilt from a recorded trace:
    a request per ``invoke`` span, submitted at its ``pick`` and done at
    its ``fetch``."""
    def spans(name):
        return np.array([(a, b) for n, a, b in t.spans if n == name]) / 1e9
    pick, invoke, fetch = spans("pick"), spans("invoke"), spans("fetch")
    model = load_module(ROOT, "models", cfg["model"])
    convs = model.convs(cfg)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    return SimpleNamespace(
        requests=len(invoke), window_s=t.window_s, setup_s=None,
        submit=pick[:, 0], invoked=invoke[:, 1], done=fetch[:, 1], chips=1,
        peaks=peaks["TPU v5 lite"], cfg=cfg, trace=t, convs=convs,
        model_flops=sum(c.flops for c in convs) + model.head_flops(cfg))


def _unzip(name, tmp_path):
    path = tmp_path / name.removesuffix(".gz")
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return path


# read at the seed of the scopes' reduction, before it existed
PINNED = {"invoke_host_ms": 1.0709061538461544,
          "systolic_ms": 0.022529384615384618,
          "systolic_roofline": 9.397402908782919,
          "xla_ops_ms": 0.12026615384615386,
          "step_mfu": 0.0033146544722650003,
          "device_idle_share": 90.81889839322018}


def test_existing_metrics_read_what_they_read_before(tmp_path):
    t = tr.load(_unzip("yolov3_tiny.xplane.pb.gz", tmp_path))
    run = _run_from_trace(t, config("yolov3-ppe_detection", cpu=True))
    got = {m: load_module(ROOT, "metrics", m).read(run) for m in PINNED}
    assert got == pytest.approx(PINNED, rel=1e-12)


def test_scoped_chip_trace_reads_the_new_metrics(tmp_path, monkeypatch):
    """A traced window of the tiny YOLOv3 (width 0.125, 64x64) served with
    named scopes and host spans on a TPU v5e, beside its compiled
    program's text (``bench/record.py``): the scope metrics and the host
    spans read, nearly every op of the program has a scope, and the
    scopes add up to every op the trace holds."""
    path = _unzip("yolov3_tiny_scopes.xplane.pb.gz", tmp_path)
    text = gzip.decompress(
        (DATA / "yolov3_tiny_scopes.hlo.txt.gz").read_bytes()).decode()
    monkeypatch.setattr(scopes, "program_text", lambda cfg, ex=None: text)
    t = tr.load(path)
    assert set(t.kernels) == {None, "systolic_matmul", "fused_affine_act"}
    run = _run_from_trace(t, config("yolov3-ppe_detection", cpu=True))
    got = {m: load_module(ROOT, "metrics", m).read(run)
           for m in ("f1_ms", "im2col_ms", "conv_roofline",
                     "systolic_roofline")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["conv_roofline"] < got["systolic_roofline"]
    host = scopes.host_ms(scopes.host_spans(path), t.window, run.requests)
    assert host["dispatch_host_ms"] > 0 and host["account_host_ms"] > 0
    by, unscoped, outside = scopes.for_run(run)
    scoped = sum(by.values())
    assert unscoped / (scoped + unscoped) < 0.01
    f1 = sum(v for k, v in by.items() if k == "f1")
    conv = scopes.conv_seconds(by)
    assert f1 * 1e3 / run.requests == pytest.approx(got["f1_ms"])
    assert f1 > 0 and conv > 0 and scoped - f1 - conv > 0   # rest of f2
    # f1, the convolutions and the rest of f2: every op the trace holds
    assert scoped == pytest.approx(t.kernel_s("") + t.xla_s(), rel=0.01)
