"""Compile the main path's Pallas kernels at real widths for a described
TPU v5e (no chip needed: the TPU compiler runs on the host).

Catches what interpret mode cannot: element types the chip lacks (the
float64 Lindley kernel was refused here), slices not aligned to the
tiling, and more VMEM than a kernel may use.  The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and pytest-xdist workers all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lindley import lindley_scan
from repro.kernels.systolic_matmul import systolic_matmul
from repro.kernels.vector_engine import fused_affine_act


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one; keep them out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


def test_lindley_kernel_compiles_at_the_10m_bucket(one_chip):
    """One shard's drive bucket of the 10^7-request, 1024-drive cell:
    128 queues of depth 16384, float32."""
    _compile(lambda a: lindley_scan(a, interpret=False), one_chip,
             (128, 16384))


@pytest.mark.parametrize("precision", ["default", "float32"])
@pytest.mark.parametrize("m,k,n", [(12544, 256, 64),     # ResNet-50 stem
                                   (49, 4608, 512)])     # layer-4 3x3 conv
def test_systolic_matmul_compiles_at_resnet50_shapes(one_chip, m, k, n,
                                                     precision):
    with jax.default_matmul_precision(precision):
        _compile(lambda x, w: systolic_matmul(x, w, interpret=False),
                 one_chip, (m, k), (k, n))


def test_flash_attention_compiles_at_vit_h14_shapes(one_chip):
    """One ViT-H/14 block's attention at 224 px: 16 heads of 80 over 257
    tokens, the whole sequence as one block."""
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=False, bq=257,
                                             bk=257, interpret=False),
             one_chip, *[(1, 16, 257, 80)] * 3)


@pytest.mark.parametrize("k,n,act", [(1280, 3840, "none"),     # qkv
                                     (1280, 5120, "gelu"),     # fc1
                                     (5120, 1280, "none")])    # fc2
def test_padded_systolic_matmul_compiles_at_vit_h14_shapes(one_chip, k, n,
                                                           act):
    """A ViT-H/14 block GEMM over 257 tokens in the tiles that
    ``ops.matmul_tiles`` picks (all 257 rows in one tile, whole K where it
    fits, the VMEM limit raised where the tiles need more than the
    default), with its bias and activation in the epilogue."""
    with jax.default_matmul_precision("float32"):
        _compile(lambda x, w, b: ops.matmul_padded(x, w, b, act=act,
                                                   interpret=False),
                 one_chip, (257, k), (k, n), (n,))


def test_fused_affine_act_compiles_at_a_224_image(one_chip):
    """f1 normalization of one 224x224x3 request: its three channel
    planes as rows, a plane per block."""
    _compile(lambda x, s, b: fused_affine_act(x, s, b, bm=224,
                                              interpret=False),
             one_chip, (3 * 224, 224), (224,), (224,))


@pytest.mark.parametrize("pipeline,size", [("asset_damage", 32),
                                           ("ppe_detection", 64)])
def test_one_named_kernel_per_convolution_in_the_served_program(
        one_chip, monkeypatch, pipeline, size):
    """The compiled f1+f2 program of a tiny vision function holds one
    ``systolic_matmul`` kernel in each convolution's ``gemm`` scope and the
    ``fused_affine_act`` kernel in f1: the names the device trace shows."""
    import re

    from repro.core.executor import DSCSExecutor
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    ex = DSCSExecutor(pipeline, image_size=size, width=0.125)
    arrays = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in ex._arrays]
    frame = jax.ShapeDtypeStruct((1, size, size, 3), jnp.uint8,
                                 sharding=one_chip)
    text = ex._infer.lower(arrays, frame).compile().as_text()
    kernels = re.findall(r'%(\w+)\.\d+ = [^\n]*custom_call_target='
                         r'"tpu_custom_call"[^\n]*op_name="jit\(infer\)/'
                         r'([^"]*)"', text)
    n_convs = sum(a.ndim == 4 for a in ex._arrays)
    gemm = sorted(int(m.group(1)) for k, scope in kernels
                  if k == "systolic_matmul"
                  for m in [re.match(r"f2/conv(\d+)/gemm/", scope)] if m)
    assert gemm == list(range(n_convs))
    assert len(kernels) == n_convs + 1
    assert [k for k, scope in kernels if scope.startswith("f1/")] == [
        "fused_affine_act"]
