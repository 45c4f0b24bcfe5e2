"""Pallas TPU kernel: W4A8 GEMM — int4 weights unpacked from their packed
int8 representation *inside the kernel* (VMEM), so HBM only ever streams
0.5 bytes/weight.  Activations are int8 (the smooth_quant path); int32 MXU
accumulation; fused per-token × per-channel dequant epilogue.

Packing layout matches ``repro.quant.int4.pack_int4``: byte b at packed
row r holds weight rows (2r, 2r+1) as (low nibble, high nibble), both
sign-extended 4-bit two's complement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack(packed):
    """(bk/2, bn) int8 → (bk, bn) int8 in [-8, 7] via arithmetic shifts."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    k2, n = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(k2 * 2, n)


def _kernel(x_ref, wp_ref, dx_ref, dw_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack(wp_ref[...])
    acc_ref[...] += jnp.dot(x_ref[...], w, preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        dx = dx_ref[...].astype(jnp.float32)
        dw = dw_ref[...].astype(jnp.float32)
        o_ref[...] = (acc * dx * dw).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def int4_matmul(
    x_int8: jax.Array,     # (M, K) int8 activations
    w_packed: jax.Array,   # (K/2, N) int8 — two int4 weights per byte
    dx: jax.Array,         # (M,) f32 per-token scale
    dw: jax.Array,         # (N,) f32 per-channel scale
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,    # must be even (pairs stay in one block)
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    M, K = x_int8.shape
    K2, N = w_packed.shape
    assert K == 2 * K2, (x_int8.shape, w_packed.shape)

    bm, bn = min(block_m, M), min(block_n, N)
    bk = min(block_k, K)
    bk += bk % 2
    Mp, Np, Kp = (-M) % bm + M, (-N) % bn + N, (-K) % bk + K
    if (Mp, Kp) != (M, K):
        x_int8 = jnp.pad(x_int8, ((0, Mp - M), (0, Kp - K)))
        dx = jnp.pad(dx, (0, Mp - M))
    if (Kp // 2, Np) != (K2, N):
        w_packed = jnp.pad(w_packed, ((0, Kp // 2 - K2), (0, Np - N)))
        dw = jnp.pad(dw, (0, Np - N))

    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
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
        name="int4_matmul",
    )(x_int8, w_packed, dx[:, None], dw[None, :])
    return out[:M, :N]
