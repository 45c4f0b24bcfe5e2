"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes with hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis optional
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.int8_matmul import (M_CAP, VMEM_BUDGET, int8_matmul,
                                       plan_blocks, vmem_bytes)
from repro.kernels.smooth_quant import smooth_quant
from repro.kernels import ops as kops


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 70),
    k=st.integers(1, 300),
    n=st.integers(1, 90),
    seed=st.integers(0, 2**31 - 1),
)
def test_int8_matmul_matches_ref(m, k, n, seed):
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = _rand(k0, (m, k))
    w = _rand(k1, (k, n))
    s = jnp.abs(_rand(k2, (k,))) + 0.3
    w_int8, w_scale = ref.quantize_symmetric(w / s[:, None], axis=0)
    xq, dx = ref.smooth_quant_ref(x, s)
    y_ref = ref.int8_matmul_ref(xq, w_int8, dx, w_scale, jnp.float32)
    y_pal = int8_matmul(xq, w_int8, dx, w_scale, out_dtype=jnp.float32,
                        block_m=32, block_n=32, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def _step_linears(D, F, Hq, Hkv, dh, L, V=None):
    """(K, N) of every W8A8 GEMM of one decode step: q, k, v, o, gate,
    up, down per layer, then the LM head where it is quantized."""
    layer = [(D, Hq * dh), (D, Hkv * dh), (D, Hkv * dh), (Hq * dh, D),
             (D, F), (D, F), (F, D)]
    return layer * L + ([(D, V)] if V else [])


# the benchmark's configurations at their published widths
# (bench/configs/); smollm-135m ties its head to the bf16 embedding
STEP_LINEARS = {
    "codeqwen1.5-7b-l8": _step_linears(4096, 13440, 32, 4, 128, 8, 92416),
    "smollm-135m": _step_linears(576, 1536, 9, 3, 64, 30),
}
# grid steps of one decode step's GEMMs at 32 slots x (gamma + 1) = 192
# rows, as PERF.md states them (61472 and 5460 under the former fixed
# 128 x 128 x 512 blocks)
STEP_GRID_192 = {"codeqwen1.5-7b-l8": 873, "smollm-135m": 270}


@pytest.mark.parametrize("M", [192, 64, 256, 1024])
@pytest.mark.parametrize("arch", sorted(STEP_LINEARS))
def test_int8_matmul_plan(arch, M):
    """Planned blocks at verify (192) and prefill rows: K blocks divide K
    (no operand is padded), one M block up to ``M_CAP`` rows, every block
    on int8's (32, 128) tiling or whole, and within the VMEM budget."""
    steps = 0
    for K, N in STEP_LINEARS[arch]:
        bm, bn, bk = plan_blocks(M, K, N)
        assert bk == K or (K % bk == 0 and bk % 128 == 0), (K, bk)
        assert bn == N or bn % 128 == 0, (N, bn)
        if M <= M_CAP:
            assert bm == M
        else:
            assert bm % 32 == 0 and bm <= M_CAP
            assert pl.cdiv(M, bm) == pl.cdiv(M, M_CAP)
        assert vmem_bytes(bm, bk, bn) <= VMEM_BUDGET, (M, K, N)
        steps += pl.cdiv(M, bm) * pl.cdiv(N, bn) * (K // bk)
    if M == 192:
        assert steps == STEP_GRID_192[arch]


@pytest.mark.parametrize("M,K,N", [
    (1000, 576, 1000),   # K with no 128-multiple divisor; M and N edges
    (40, 4000, 1700),    # whole N over the budget: a ragged last N block
    (192, 1536, 576),    # smollm-135m down-projection at verify rows
])
def test_int8_matmul_planned_blocks_exact(M, K, N):
    """The planned blocks, with no override, give the reference's f32
    output bit for bit, edge blocks included."""
    bm, bn, bk = plan_blocks(M, K, N)
    if (M, K, N) == (1000, 576, 1000):
        assert bk == K and M % bm and N % bn
    elif (M, K, N) == (40, 4000, 1700):
        assert K % 128 and N % bn
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(M + K + N), 4)
    xq = jax.random.randint(k0, (M, K), -127, 128, jnp.int8)
    w_int8 = jax.random.randint(k1, (K, N), -127, 128, jnp.int8)
    dx = jax.random.uniform(k2, (M,), jnp.float32, 1e-3, 2e-2)
    w_scale = jax.random.uniform(k3, (N,), jnp.float32, 1e-3, 2e-2)
    y = int8_matmul(xq, w_int8, dx, w_scale, out_dtype=jnp.float32,
                    interpret=True)
    y_ref = ref.int8_matmul_ref(xq, w_int8, dx, w_scale, jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 80),
    k=st.integers(1, 260),
    seed=st.integers(0, 2**31 - 1),
)
def test_smooth_quant_matches_ref(m, k, seed):
    k0, k1 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k0, (m, k), scale=3.0)
    s = jnp.abs(_rand(k1, (k,))) + 0.2
    q_pal, dx_pal = smooth_quant(x, s, block_m=16, interpret=True)
    q_ref, dx_ref = ref.smooth_quant_ref(x, s)
    assert bool(jnp.all(q_pal == q_ref))
    np.testing.assert_allclose(np.asarray(dx_pal), np.asarray(dx_ref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", [(128, 512, 128), (37, 130, 65), (1, 64, 256)])
def test_w8a8_pipeline_dtypes(dtype, mkn):
    m, k, n = mkn
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = _rand(k0, (m, k), dtype)
    w = _rand(k1, (k, n))
    s = jnp.abs(_rand(k2, (k,))) + 0.5
    w_int8, w_scale = ref.quantize_symmetric(w / s[:, None], axis=0)
    xq, dx = smooth_quant(x, s, interpret=True)
    y = int8_matmul(xq, w_int8, dx, w_scale, out_dtype=dtype, interpret=True)
    y_ref = ref.w8a8_matmul_ref(x, w_int8, w_scale, s, out_dtype=dtype)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_w8a8_quantization_error_small():
    """The W8A8 GEMM must approximate the true matmul well (paper §3.2)."""
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(7), 3)
    x = _rand(k0, (64, 512))
    w = _rand(k1, (512, 256))
    s = jnp.ones((512,))
    w_int8, w_scale = ref.quantize_symmetric(w, axis=0)
    y = ref.w8a8_matmul_ref(x, w_int8, w_scale, s, out_dtype=jnp.float32)
    y_true = x @ w
    rel = float(jnp.linalg.norm(y - y_true) / jnp.linalg.norm(y_true))
    assert rel < 0.05, rel


def test_ops_dispatch_batched_shapes():
    """Public wrapper handles leading batch dims."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(1))
    x = _rand(k0, (2, 3, 5, 96))
    w = _rand(k1, (96, 64))
    s = jnp.ones((96,))
    w_int8, w_scale = ref.quantize_symmetric(w, axis=0)
    y = kops.w8a8_matmul(x, w_int8, w_scale, s)
    assert y.shape == (2, 3, 5, 64)
    y2 = ref.w8a8_matmul_ref(x.reshape(-1, 96), w_int8, w_scale, s, jnp.float32)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(1, 48),
    k=st.integers(1, 120),
    n=st.integers(1, 70),
    seed=st.integers(0, 2**31 - 1),
)
def test_int4_matmul_matches_unpacked(m, k, n, seed):
    from repro.kernels.int4_matmul import int4_matmul
    from repro.quant.int4 import pack_int4, quantize_symmetric_int4

    k = k * 2  # even K for packing
    k0, k1 = jax.random.split(jax.random.PRNGKey(seed))
    x = _rand(k0, (m, k))
    w = _rand(k1, (k, n))
    q, dw = quantize_symmetric_int4(w, axis=0)
    xq, dx = ref.smooth_quant_ref(x, jnp.ones((k,)))
    y = int4_matmul(xq, pack_int4(q), dx, dw, out_dtype=jnp.float32,
                    block_m=16, block_n=32, block_k=64, interpret=True)
    acc = jax.lax.dot_general(xq, q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y_ref = acc.astype(jnp.float32) * dx[:, None] * dw[None, :]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_pallas_path_forced(monkeypatch):
    """REPRO_USE_PALLAS routes the public op through interpret-mode Pallas."""
    kops.set_use_pallas(True)
    try:
        k0, k1 = jax.random.split(jax.random.PRNGKey(2))
        x = _rand(k0, (17, 48))
        w = _rand(k1, (48, 32))
        s = jnp.ones((48,))
        w_int8, w_scale = ref.quantize_symmetric(w, axis=0)
        y = kops.w8a8_matmul(x, w_int8, w_scale, s)
        y_ref = ref.w8a8_matmul_ref(x, w_int8, w_scale, s, jnp.float32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
    finally:
        kops.set_use_pallas(False)
