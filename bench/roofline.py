"""Operations and bytes that a kernel call needs, from its shapes alone,
and the least time the chip could take for them.

Nothing here reads the compiler's cost analysis: it counts what the
algorithm must move and compute, so that padding, re-fetches and work on
idle rows show as a lower share of the roofline and not as more work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; unknown kinds are an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def int8_matmul_cost(M: int, K: int, N: int) -> tuple:
    """(int8 ops, bytes) of one W8A8 GEMM ``(M, K) x (K, N)``: int8
    activations and weights in, an f32 scale per row and per column in,
    a bf16 ``(M, N)`` result out."""
    ops = 2 * M * K * N
    nbytes = M * K + K * N + 4 * M + 4 * N + 2 * M * N
    return ops, nbytes


def flash_decode_paged_cost(T: int, Hq: int, Hkv: int, dh: int,
                            keys: Iterable[int], kv_bytes: int,
                            scales: bool) -> tuple:
    """(flops, bytes) of one paged verify-attention call.

    ``keys`` holds, per live row, the number of cache positions its
    window attends (committed context plus the window).  Bytes are the
    live positions' K and V (and their f32 scales when the cache is
    int8), the bf16 queries and the bf16 output; not the blocks the grid
    walks.  Flops are ``Q K^T`` and ``P V`` over the live positions."""
    keys = list(keys)
    per_pos = 2 * Hkv * dh * kv_bytes + (2 * Hkv * 4 if scales else 0)
    nbytes = sum(keys) * per_pos + 2 * 2 * len(keys) * T * Hq * dh
    flops = sum(4 * T * Hq * dh * k for k in keys)
    return flops, nbytes


def least_seconds(ops: float, nbytes: float, peak_ops: float,
                  peak_bytes: float) -> tuple:
    """(least time, which bound) for work of ``ops`` and ``nbytes``."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def dense_linears(D: int, F: int, Hq: int, Hkv: int, dh: int) -> list:
    """(K, N) of the seven linears of one dense block, in call order:
    q, k, v, o, gate, up, down."""
    return [(D, Hq * dh), (D, Hkv * dh), (D, Hkv * dh), (Hq * dh, D),
            (D, F), (D, F), (F, D)]


def step_int8_matmul_calls(d: dict, rows: int, quantized_head: bool
                           ) -> list:
    """(M, K, N) of every W8A8 GEMM in one decode step of ``rows`` rows."""
    calls = [(rows, K, N) for _ in range(d["L"])
             for K, N in dense_linears(d["D"], d["F"], d["H"], d["Hkv"],
                                       d["dh"])]
    if quantized_head:
        calls.append((rows, d["D"], d["V"]))
    return calls


def model_flops_per_token(d: dict) -> int:
    """2 x the parameters a token multiplies by: every block's linears and
    the LM head (the embedding lookup is free)."""
    per_layer = sum(K * N for K, N in dense_linears(
        d["D"], d["F"], d["H"], d["Hkv"], d["dh"]))
    return 2 * (d["L"] * per_layer + d["D"] * d["V"])


def attention_flops(d: dict, context: Sequence[int]) -> int:
    """Flops of attention for tokens at the given context lengths
    (``Q K^T`` and ``P V``, every layer)."""
    return sum(4 * d["L"] * d["H"] * d["dh"] * c for c in context)
