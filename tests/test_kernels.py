"""Per-kernel shape/dtype sweeps: pallas_call (interpret) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.lindley import error_bound

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (64, 128, 256), (8, 16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "relu", "gelu"])
def test_systolic_matmul(m, k, n, dtype, act):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (k, n), jnp.float32).astype(dtype)
    b = jax.random.normal(k3, (n,), jnp.float32).astype(dtype)
    got = ops.matmul(x, w, b, act=act, bm=min(64, m), bn=min(64, n),
                     bk=min(64, k))
    want = ref.matmul_ref(x, w, b, act=act)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.05 if dtype == jnp.bfloat16 else 1e-4,
                               atol=_tol(dtype) * max(1, k // 64))


def test_matmul_padded_arbitrary_shapes():
    x = jax.random.normal(KEY, (37, 147))
    w = jax.random.normal(KEY, (147, 53))
    got = ops.matmul_padded(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-3)


# ViT-H/14's block GEMMs over 257 tokens (qkv, out, fc1, fc2), then
# ResNet-50's stem and layer-4 3x3 convolution and YOLOv3's stem
VIT_BLOCK_GEMMS = [(257, 1280, 3840), (257, 1280, 1280), (257, 1280, 5120),
                   (257, 5120, 1280)]
SERVED_GEMMS = VIT_BLOCK_GEMMS + [(12544, 147, 64), (49, 4608, 512),
                                  (173056, 27, 32)]


@pytest.mark.parametrize("m,k,n", SERVED_GEMMS)
def test_matmul_tiles_are_legal_and_fit_the_budget(m, k, n):
    from repro.kernels.systolic_matmul import tile_vmem_bytes
    bm, bk, bn = ops.matmul_tiles(m, k, n)
    assert bm % 8 == 0 or bm == m
    assert bk % 128 == 0 or bk == k
    assert bn % 128 == 0 or bn == n
    assert bm <= ops.MAX_ROW_TILE
    assert tile_vmem_bytes(bm, bk, bn) <= ops.TILE_VMEM_BUDGET
    # padded at most to the next multiple of 128 (K, N) or of 8 a row tile
    assert ops._round_up(k, bk) <= ops._round_up(k, 128)
    assert ops._round_up(n, bn) <= ops._round_up(n, 128)
    assert ops._round_up(m, bm) - m < 8 * -(-m // bm)


@pytest.mark.parametrize("m,k,n", VIT_BLOCK_GEMMS)
def test_matmul_tiles_cover_vit_tokens_in_one_row_tile(m, k, n):
    """All 257 tokens in one row tile, so each weight tile is read once."""
    bm, _, _ = ops.matmul_tiles(m, k, n)
    assert bm == m


def _pallas_grids(jaxpr):
    """(kernel name, grid steps) of every ``pallas_call`` in ``jaxpr``."""
    import math
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield (eqn.params["name"],
                   math.prod(eqn.params["grid_mapping"].grid))
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_grids(sub)


def test_vit_h14_request_takes_under_1000_systolic_grid_steps():
    """The served ViT-H/14 program at published widths (224 px, 257
    tokens; shapes only) runs its 130 GEMMs in under 1,000 grid steps of
    the systolic kernel (115,300 with 128x128x128 tiles)."""
    from repro.models import vision
    kept = {}

    def init():
        leaves, treedef = jax.tree_util.tree_flatten(
            vision.vit_init(jax.random.PRNGKey(0)))
        kept.update(leaves=leaves, treedef=treedef)
        return [v for v in leaves if isinstance(v, jax.Array)]

    def apply(arrays, frame):
        it = iter(arrays)
        p = jax.tree_util.tree_unflatten(
            kept["treedef"], [next(it) if isinstance(v, jax.Array) else v
                              for v in kept["leaves"]])
        return vision.vit_apply(p, frame, use_kernel=True)

    jaxpr = jax.make_jaxpr(apply)(
        jax.eval_shape(init), jax.ShapeDtypeStruct((1, 224, 224, 3),
                                                   jnp.float32))
    steps = [s for name, s in _pallas_grids(jaxpr.jaxpr)
             if name == "systolic_matmul"]
    assert len(steps) == 32 * 4 + 2
    assert sum(steps) < 1000, sum(steps)


@pytest.mark.parametrize("m,k,n,act,budget,rows,case", [
    (257, 96, 640, "gelu", None, None, "ragged M whole, bn steps"),
    (64, 2048, 256, "relu", 1 << 20, None, "K split"),
    (200, 130, 300, "gelu", None, 64, "M padded, ragged K and N whole"),
    (256, 40, 384, "none", None, 64, "M split exactly")])
def test_matmul_padded_tiles_from_shape(monkeypatch, m, k, n, act, budget,
                                        rows, case):
    """``matmul_padded`` with the tiles of ``matmul_tiles`` against
    ``x @ w + b``: a budget or a row cap shrunk so that small shapes take
    each path of the rule."""
    if budget:
        monkeypatch.setattr(ops, "TILE_VMEM_BUDGET", budget)
    if rows:
        monkeypatch.setattr(ops, "MAX_ROW_TILE", rows)
    bm, bk, bn = ops.matmul_tiles(m, k, n)
    steps = -(-m // bm) * -(-k // bk) * -(-n // bn)
    assert {"ragged M whole, bn steps": bm == m and bk == k and steps >= 4,
            "K split": bk < k and bk % 128 == 0,
            "M padded, ragged K and N whole": (m % bm and bk == k
                                               and bn == n),
            "M split exactly": bm < m and m % bm == 0}[case]
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    w = jax.random.normal(k2, (k, n), jnp.float32)
    b = jax.random.normal(k3, (n,), jnp.float32)
    with jax.default_matmul_precision("float32"):
        got = ops.matmul_padded(x, w, b, act=act)
        want = ref.matmul_ref(x, w, b, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("b,h,kv,sq,skv,d", [
    (2, 8, 2, 128, 128, 64), (1, 4, 1, 64, 128, 32), (2, 4, 4, 128, 64, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 48)])
def test_flash_attention(b, h, kv, sq, skv, d, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kv, skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kv, skv, d), jnp.float32)
    got = ops.attention(q, k, v, causal=causal, window=window, bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("s", [5, 10, 257])
@pytest.mark.parametrize("d", [8, 80])
@pytest.mark.parametrize("block", [32, "whole"])
def test_flash_attention_ragged_non_causal(s, d, block):
    """A length that is not a multiple of the block (257 is prime), at
    ViT-H/14's head dim 80: padded up to whole blocks with the padded keys
    given no weight, or taken whole as one block."""
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (1, 2, s, d), jnp.float32) for kk in ks)
    b = s if block == "whole" else block
    got = ops.attention(q, k, v, causal=False, bq=b, bk=b)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtype(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 64, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 64, 32)).astype(dtype)
    got = ops.attention(q, k, v, bq=32, bk=32)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.05, atol=0.03)


@pytest.mark.parametrize("m,n", [(256, 256), (64, 384), (8, 128)])
@pytest.mark.parametrize("act", ["silu", "sigmoid", "tanh"])
def test_vector_engine_affine(m, n, act):
    x = jax.random.normal(KEY, (m, n))
    s = jax.random.normal(KEY, (n,))
    b = jax.random.normal(KEY, (n,))
    got = ops.affine_act(x, s, b, act=act)
    want = ref.affine_act_ref(x, s, b, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_vector_engine_quant_roundtrip():
    x = jax.random.normal(KEY, (128, 256)) * 3.0
    q, s = ops.quantize(x)
    qr, sr = ref.quantize_int8_ref(x)
    assert int(jnp.sum(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32)))) == 0
    xd = ops.dequantize(q, s)
    # int8 symmetric quantization error bound: scale/2 per element
    assert float(jnp.max(jnp.abs(xd - x))) <= float(jnp.max(s)) * 0.51


@pytest.mark.parametrize("b,s,w", [(2, 64, 128), (4, 128, 256), (1, 32, 128)])
@pytest.mark.slow
def test_rglru_kernel(b, s, w):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, s, w)) * 0.2
    gx = jax.random.normal(ks[1], (b, s, w))
    ga = jax.random.normal(ks[2], (b, s, w))
    la = jax.random.normal(ks[3], (w,))
    h0 = jax.random.normal(ks[0], (b, w)) * 0.1
    got = ops.rglru(x, gx, ga, la, h0)
    want = ref.rglru_ref(x, gx, ga, la, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# Lindley tests run in the CI kernel-smoke step: keep them small and
# NOT slow-marked.
@pytest.mark.parametrize("r,w", [(3, 17), (128, 128), (200, 300), (1, 1)])
def test_lindley_kernel_vs_ref(r, w):
    """The float32 device solve stays within its stated absolute bound
    of the float64 numpy oracle, element by element."""
    rng = np.random.default_rng(11)
    t = np.sort(rng.uniform(0.0, 100.0, size=(r, w)), axis=1)
    s = rng.uniform(1e-3, 4.0, size=(r, w))
    got = ops.lindley(t, s)
    want = ref.lindley_ref(t, s)
    assert got.dtype == np.float64
    assert np.all(np.abs(got - want) <= error_bound(t, s))


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("nserv,n", [(6, 500), (1, 700), (40, 64)])
def test_lindley_kernel_bit_equal_to_numpy_backend(seed, nserv, n):
    """``backend='pallas'`` through the segmented flat layout agrees
    with the float64 ``segmented`` backend within the kernel's error
    bound on every request (it is no longer bit-equal: the device works
    in float32), and is exact at idle starts."""
    from repro.core import lindley as core_lindley

    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, nserv, size=n))
    t = rng.uniform(0.0, 60.0, size=n)
    seg = core_lindley.segment_fenceposts(keys, 0, nserv)
    for j in range(nserv):
        t[seg[j]:seg[j + 1]].sort()
    s = rng.uniform(1e-3, 3.0, size=n)
    out = {}
    for backend in ("segmented", "pallas"):
        start = np.empty(n)
        fin = np.empty(n)
        core_lindley.solve_segments(seg, t, s, start, fin, backend=backend)
        out[backend] = (start, fin)
    bound = core_lindley.segment_error_bound(seg, t, s)
    for col in (0, 1):
        diff = np.abs(out["pallas"][col] - out["segmented"][col])
        assert np.all(diff <= bound)
    idle = out["segmented"][0] == t
    assert np.array_equal(out["pallas"][0][idle], t[idle])


def test_lindley_x64_scoped_to_the_call():
    """ops.lindley returns float64 starts without turning on x64 for the
    call or the process: the global default dtype stays float32."""
    t = np.array([[0.0, 0.5, 1.0]])
    s = np.array([[1.0, 1.0, 1.0]])
    got = ops.lindley(t, s)
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, np.array([[0.0, 1.0, 2.0]]))
    assert not jax.config.jax_enable_x64
    assert jnp.asarray(1.5).dtype == jnp.float32


def test_lindley_error_bound_resets_at_idle_starts():
    """The bound does not grow with simulated time: a queue that idles
    before every arrival has no float32 error at all, however late."""
    t = np.arange(1, 6, dtype=np.float64)[None, :] * 1e6
    s = np.full_like(t, 0.5)
    b = error_bound(t, s)
    assert np.all(b < 1e-6)
    np.testing.assert_array_equal(ops.lindley(t, s), t)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 32, 2, 16, 32), (1, 256, 2, 16, 1, 8, 64),
    (2, 64, 4, 16, 4, 16, 64)])
@pytest.mark.slow
def test_ssd_kernel(b, s, h, p, g, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.4
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.4)
    Bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y, hf = ops.ssd(x, dt, A, Bm, Cm, chunk=chunk)
    yr, hfr = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr),
                               rtol=1e-3, atol=1e-3)


def test_compile_cache_is_fixed_in_the_checkout_or_placed_by_env(
        monkeypatch):
    """Entry points keep JAX's compile cache in one fixed, git-ignored
    directory of the checkout, unless JAX_COMPILATION_CACHE_DIR places
    it; importing sets nothing."""
    import importlib
    import pathlib

    from repro import jax_cache
    before = jax.config.jax_compilation_cache_dir
    importlib.reload(jax_cache)
    assert jax.config.jax_compilation_cache_dir == before
    root = pathlib.Path(__file__).resolve().parent.parent
    assert jax_cache.CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root / "x"))
        jax_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jax_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            jax_cache.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
