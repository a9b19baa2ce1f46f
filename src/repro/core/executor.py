"""End-to-end pipeline executor: runs Table I pipelines NUMERICALLY on JAX
(the near-storage DSA path uses the Pallas kernels), while the analytical
models account latency/energy/cost for the deployment being simulated.

This is the bridge between the paper's system model and the real compute
substrate: f1 pre-processing runs on the vector engine (normalize / cast /
quantize), f2 inference on the systolic kernels, f3 post-processing on the
host — matching Fig. 2 / Fig. 3(b).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.energy import pipeline_energy_j
from repro.core.function import Pipeline, standard_pipeline
from repro.core.latency import LatencyModel
from repro.core.platforms import PLATFORMS, Platform
from repro.kernels import ops
from repro.models import vision


@dataclass
class ExecutionReport:
    result: Any
    latency_breakdown: Dict[str, float]
    energy_breakdown: Dict[str, float]
    platform: str
    accelerated: bool
    output: Any              # f2's raw output (logits), before f3


def _preprocess_vector_engine(img: jax.Array, use_kernel: bool) -> jax.Array:
    """f1: normalize + cast — the DSA vector engine's job.  The kernel
    streams the frame one channel plane (H, W) per block, so its output is
    laid out by plane: lane-dense for three channels, where a
    channel-minor layout would fill 3 of 128 lanes."""
    if use_kernel:
        B, H, W, C = img.shape
        planes = jnp.transpose(img, (0, 3, 1, 2)).reshape(B * C * H, W)
        out = ops.affine_act(planes.astype(jnp.float32),
                             jnp.full((W,), 1.0 / 127.5), jnp.full((W,), -1.0),
                             act="none", bm=H)
        return jnp.transpose(out.reshape(B, C, H, W), (0, 2, 3, 1))
    flat = img.reshape(img.shape[0], -1).astype(jnp.float32)
    n = flat.shape[1]
    scale = jnp.full((n,), 1.0 / 127.5)
    bias = jnp.full((n,), -1.0)
    return (flat * scale + bias).reshape(img.shape)


_MODEL_BUILDERS: Dict[str, Tuple[Callable, Callable, dict]] = {
    "asset_damage": (vision.resnet50_init, vision.resnet50_apply,
                     {"width": 0.125}),
    "content_moderation": (vision.effnet_init, vision.effnet_apply,
                           {"width": 0.25}),
    "clinical": (vision.fcn_init, vision.fcn_apply, {"width": 0.125}),
    "ppe_detection": (vision.yolov3_init, vision.yolov3_apply,
                      {"width": 0.125}),
    # 16-pixel patches, so that the executor's default frames tile
    "remote_sensing": (vision.vit_init, vision.vit_apply,
                       {"width": 0.1, "patch": 16}),
}


def _split_arrays(tree) -> Tuple[list, Callable]:
    """The array leaves of ``tree`` and a function that rebuilds it from
    them: non-array leaves (strides, head counts) stay Python constants
    when the model is jitted."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    is_arr = [isinstance(v, jax.Array) for v in leaves]

    def rebuild(arrays):
        it = iter(arrays)
        return jax.tree_util.tree_unflatten(
            treedef, [next(it) if a else v for v, a in zip(leaves, is_arr)])
    return [v for v, a in zip(leaves, is_arr) if a], rebuild


class DSCSExecutor:
    """Executes one Table I pipeline end-to-end in a chosen deployment.

    Vision pipelines run f1 and f2 as one jitted program.  ``width``
    serves the published model at that channel multiplier (``1.0`` is the
    published width); left out, the reduced model of ``_MODEL_BUILDERS``.
    For ViT (``remote_sensing``) the published model is ViT-H/14: width
    ``1280 * width``, MLP ``5120 * width``, 16 heads, 32 layers, 14-pixel
    patches, so ``image_size`` must be a multiple of 14.
    """

    def __init__(self, workload_name: str, *, platform: str = "DSCS-Serverless",
                 image_size: int = 64, seed: int = 0,
                 width: Optional[float] = None):
        self.pipeline = standard_pipeline(
            workload_name, accelerate=(platform == "DSCS-Serverless"))
        self.platform = PLATFORMS[platform]
        self.lm = LatencyModel(seed=seed)
        self.image_size = image_size
        self.calls = 0
        key = jax.random.PRNGKey(seed)
        if workload_name in _MODEL_BUILDERS:
            init, apply, kw = _MODEL_BUILDERS[workload_name]
            if width is not None:
                kw = {"width": width}
            self.params = init(key, **kw)
            self._arrays, rebuild = _split_arrays(self.params)
            accel = self.platform.kind == "dsa"

            def infer(arrays, request):
                with jax.named_scope("f1"):
                    x = (_preprocess_vector_engine(request, accel)
                         if request.dtype == jnp.uint8 else request)
                with jax.named_scope("f2"):
                    return apply(rebuild(arrays), x, use_kernel=accel)
            self._infer = jax.jit(infer)
        elif workload_name == "credit_risk":
            self.params = jax.random.normal(key, (200, 1)) * 0.1
            self._apply = lambda p, x, use_kernel=False: jax.nn.sigmoid(x @ p)
        else:  # chatbot / translation: tiny LM via the transformer family
            from repro.configs import get_arch
            from repro.models import transformer as T
            cfg = get_arch("qwen3-8b").reduced()
            self.params = T.init_params(cfg, key)
            self._cfg = cfg
            self._apply = lambda p, x, use_kernel=False: T.forward(
                self._cfg, p, x)

    def make_request(self, key: jax.Array) -> jax.Array:
        name = self.pipeline.name
        if name == "credit_risk":
            return jax.random.normal(key, (1, 200))
        if name in ("chatbot", "translation"):
            return jax.random.randint(key, (1, 32), 0, 512)
        s = self.image_size
        return jax.random.randint(key, (1, s, s, 3), 0, 256).astype(jnp.uint8)

    def lower(self, request: jax.Array):
        """The lowered f1+f2 program a vision pipeline runs for
        ``request`` (``.as_text()`` shows whether kernels compiled)."""
        return self._infer.lower(self._arrays, request)

    def __call__(self, request: jax.Array) -> ExecutionReport:
        """Serve one request.  Its host work shows in a profiler trace as
        the spans ``f1f2`` (dispatch of the f1+f2 program), ``f3`` (the
        top-1's dispatch) and ``account`` (the analytic latency and energy
        model), each carrying the call's number as ``call``."""
        accel = self.platform.kind == "dsa"
        name = self.pipeline.name
        self.calls += 1
        # f1 — pre-process (vector engine), f2 — inference (systolic
        # kernels on the DSA path)
        with TraceAnnotation("f1f2", call=self.calls):
            if name in _MODEL_BUILDERS:
                y = self._infer(self._arrays, request)
            else:
                y = self._apply(self.params, request)
        # f3 — post/notify
        with TraceAnnotation("f3", call=self.calls):
            if y.ndim >= 2 and y.shape[-1] > 1:
                result = jnp.argmax(y, axis=-1)
            else:
                result = y
        with TraceAnnotation("account", call=self.calls):
            lat = self.lm.pipeline_breakdown(self.platform,
                                             self.pipeline.workload)
            en = pipeline_energy_j(self.lm, self.platform,
                                   self.pipeline.workload)
        return ExecutionReport(result=result, latency_breakdown=lat,
                               energy_breakdown=en,
                               platform=self.platform.name, accelerated=accel,
                               output=y)
