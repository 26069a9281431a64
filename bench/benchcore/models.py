"""Operation and byte models of the served path, computed from shapes.

The KV-read models are copies of `repro.roofline.analysis.decode_kv_bytes`
and `paged_gather_bytes`, restricted to the layer kinds the benchmark's
configurations have (full and windowed attention); a test pins the copies
equal to the originals at the cells' shapes. The model FLOPs per token and
the kernels' operand bytes are the benchmark's own.

A `Shape` is built from a configuration file alone, by the file's model
family (`families/<family>.py` `shape`), so the yardstick needs nothing
from the program; everything here reads only the `Shape`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Iterable, Optional, Sequence

KV_DTYPE_BYTES = {"float32": 4, "bf16": 2, "bfloat16": 2, "float16": 2,
                  "int8": 1, "fp8": 1}
_QUANTIZED_KV = ("int8", "fp8")
SCALE_BYTES = 4                 # one f32 scale per quantized group
ACT_BYTES = 2                   # bf16 activations


@dataclass(frozen=True)
class Layer:
    """What one layer costs a token."""
    matmul_params: int          # weights a token multiplies through (an
                                # expert layer: its active and shared
                                # experts and the router)
    attn_flops_per_key: int     # attention FLOPs per token and key
                                # (GQA: 4 H dh, scores and values)
    attn_io: int                # query and output elements of a token
                                # in the decode kernel (GQA: 2 H dh)
    kv_values: int              # K/V elements of one cached row
                                # (GQA: 2 Hk dh)
    kv_scales: int              # scales of one row of a quantized cache
                                # (GQA: 2 Hk, one per kv head for K and V)
    window: int = 0             # 0 = full attention


@dataclass(frozen=True)
class Shape:
    d_model: int                # width of one AltUp block
    vocab: int
    altup_k: int
    recycled: bool
    layers: tuple               # one Layer per layer, in order

    @property
    def n_layers(self) -> int:
        return len(self.layers)


def _kv_row(layer: Layer, kv_dtype: str) -> int:
    scale_b = SCALE_BYTES if kv_dtype in _QUANTIZED_KV else 0
    return layer.kv_values * KV_DTYPE_BYTES[kv_dtype] \
        + layer.kv_scales * scale_b


def decode_kv_bytes(s: Shape, lengths: Iterable[int], *, T: int,
                    kv_dtype: str = "bf16", ragged: bool = True) -> float:
    """KV-cache bytes read by one decode step's attention, whole model:
    each slot's fill depth (ragged) or the whole allocation (dense);
    windowed layers cap a slot's rows at the window."""
    lengths = [int(x) for x in lengths]
    total = 0.0
    for layer in s.layers:
        w = layer.window
        cap = min(T, w) if w > 0 else T
        rows = (sum(min(ln, cap) for ln in lengths) if ragged
                else len(lengths) * cap)
        total += rows * _kv_row(layer, kv_dtype)
    return total


def paged_gather_bytes(s: Shape, lengths: Iterable[int], *, page: int,
                       T: int, kv_dtype: str = "bf16") -> Dict[str, float]:
    """The paged read: whole pages per slot and layer, plus the block
    table and the lengths the kernel prefetches."""
    lengths = [int(x) for x in lengths]
    kv_total = 0.0
    for layer in s.layers:
        w = layer.window
        cap = min(T, w) if w > 0 else T
        kv_total += sum(-(-min(ln, cap) // page) * page
                        for ln in lengths) * _kv_row(layer, kv_dtype)
    B = len(lengths)
    table = 4.0 * B * -(-T // page) + 4.0 * B
    exact = decode_kv_bytes(s, lengths, T=T, kv_dtype=kv_dtype)
    total = kv_total + table
    return {"kv_bytes": kv_total, "table_bytes": table, "total": total,
            "overhead_frac": total / exact if exact > 0 else 0.0}


# ---------------------------------------------------------------- kernels

def attn_kernel_cost(s: Shape, lengths: Sequence[int], *, T: int,
                     kv_dtype: str = "bf16", page: int = 0,
                     layer: Optional[Layer] = None):
    """(flops, bytes) of one layer's S=1 decode attention call over slots
    at fill depths `lengths`: the KV rows the slots need (page-granular
    for a paged pool, with its table), the query in and the output out.
    `layer` defaults to the first."""
    layer = layer or s.layers[0]
    one = dataclasses.replace(s, layers=(layer,))
    if page:
        kv = paged_gather_bytes(one, lengths, page=page, T=T,
                                kv_dtype=kv_dtype)["total"]
    else:
        kv = decode_kv_bytes(one, lengths, T=T, kv_dtype=kv_dtype)
    B = len(lengths)
    qo = B * layer.attn_io * ACT_BYTES + 4 * B
    flops = float(layer.attn_flops_per_key) * sum(int(x) for x in lengths)
    return flops, kv + qo


def altup_kernel_cost(s: Shape, rows: int):
    """(flops, bytes) of one layer's fused predict+correct over `rows`
    token rows: the widened stream in and out, the layer output in, and
    the K x K predictor, K correctors and K selector entries."""
    K, d = s.altup_k, s.d_model
    by = rows * (2 * K * d + d) * ACT_BYTES + (K * K + 2 * K) * 4
    flops = rows * (2.0 * K * K * d + 3.0 * K * d)
    return flops, by


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


# -------------------------------------------------------------- model flops

def matmul_params(s: Shape) -> float:
    """Weights every token multiplies through: those of each layer, and
    the output head, tied or not (K*d wide for the widened embedding, d
    wide for the recycled one). The input lookup is not a matmul."""
    d = s.d_model
    head = s.vocab * (d if s.recycled else s.altup_k * d)
    return sum(layer.matmul_params for layer in s.layers) + head


def span_flops(s: Shape, start: int, n: int) -> float:
    """Model FLOPs of n consecutive tokens at 0-based positions
    start..start+n-1: 2 per matmul weight, causal attention over
    position + 1 keys in each layer (its FLOPs per key), and AltUp's
    predict and correct (2K^2 d + 3K d per layer)."""
    K, d = s.altup_k, s.d_model
    altup = s.n_layers * (2.0 * K * K * d + 3.0 * K * d) if K > 1 else 0.0
    attn = 0.0
    for layer in s.layers:
        w = layer.window
        if w > 0:
            keys = sum(min(start + i + 1, w) for i in range(n))
        else:
            keys = n * start + n * (n + 1) / 2
        attn += layer.attn_flops_per_key * keys
    return n * (2.0 * matmul_params(s) + altup) + attn
