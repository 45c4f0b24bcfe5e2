"""Pallas TPU kernel: W8A8 INT8 GEMM with INT32 MXU accumulation and a fused
per-token × per-channel dequantization epilogue (paper Eq. 8 / Eq. 10).

TPU adaptation notes (vs. the paper's Ascend NPU kernel):
  * the MXU natively consumes int8×int8→int32 via
    ``jnp.dot(..., preferred_element_type=jnp.int32)``;
  * blocks are 128-aligned to match the MXU systolic array and VMEM tiling;
  * the int32 accumulator lives in a VMEM scratch tile that is reused across
    the K grid dimension (innermost, "arbitrary" semantics), so partial sums
    never round-trip to HBM;
  * dequant scales (Δx row-block, Δw col-block) are streamed into VMEM with
    their own BlockSpecs and applied in the epilogue on the last K step —
    the FP output is written to HBM exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, dx_ref, dw_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        dx = dx_ref[...].astype(jnp.float32)   # (bm, 1)
        dw = dw_ref[...].astype(jnp.float32)   # (1, bn)
        o_ref[...] = (acc * dx * dw).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def int8_matmul(
    x_int8: jax.Array,    # (M, K) int8
    w_int8: jax.Array,    # (K, N) int8
    dx: jax.Array,        # (M,) f32
    dw: jax.Array,        # (N,) f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    M, K = x_int8.shape
    K2, N = w_int8.shape
    assert K == K2, (x_int8.shape, w_int8.shape)

    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    # pad to block multiples (zero int8 rows/cols contribute 0 to the int32 acc)
    Mp, Np, Kp = (-M) % bm + M, (-N) % bn + N, (-K) % bk + K
    if (Mp, Kp) != (M, K):
        x_int8 = jnp.pad(x_int8, ((0, Mp - M), (0, Kp - K)))
        dx = jnp.pad(dx, (0, Mp - M))
    if (Kp, Np) != (K, N):
        w_int8 = jnp.pad(w_int8, ((0, Kp - K), (0, Np - N)))
        dw = jnp.pad(dw, (0, Np - N))

    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="int8_matmul",
    )(x_int8, w_int8, dx[:, None], dw[None, :])
    return out[:M, :N]
