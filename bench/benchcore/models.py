"""Operation and byte models of the served path, computed from shapes.

The KV-read models are copies of `repro.roofline.analysis.decode_kv_bytes`
and `paged_gather_bytes`, restricted to the layer kinds the benchmark's
configurations have (full and windowed attention); a test pins the copies
equal to the originals at the cells' shapes. The model FLOPs per token and
the kernels' operand bytes are the benchmark's own.

`Shape` is built from a configuration file alone (`shape_of`), so the
yardstick needs nothing from the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Sequence

KV_DTYPE_BYTES = {"float32": 4, "bf16": 2, "bfloat16": 2, "float16": 2,
                  "int8": 1, "fp8": 1}
_QUANTIZED_KV = ("int8", "fp8")
SCALE_BYTES = 4                 # one f32 scale per (position, kv head)
ACT_BYTES = 2                   # bf16 activations


@dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    altup_k: int
    recycled: bool
    windows: tuple              # per layer, 0 = full attention


def shape_of(config: dict) -> Shape:
    m = config["model"]
    n_layers = int(m["num_hidden_layers"])
    heads = int(m["num_attention_heads"])
    d = int(m["hidden_size"])
    k = int(config["altup"]["K"])
    return Shape(n_layers=n_layers, d_model=d, n_heads=heads,
                 n_kv_heads=int(m["num_key_value_heads"]),
                 head_dim=int(m.get("head_dim") or d // heads),
                 d_ff=int(m["intermediate_size"]),
                 vocab=int(m["vocab_size"]), altup_k=k,
                 recycled=bool(config["altup"]["recycled"]),
                 windows=tuple([0] * n_layers))


def _kv_row(s: Shape, kv_dtype: str) -> int:
    scale_b = SCALE_BYTES if kv_dtype in _QUANTIZED_KV else 0
    return 2 * s.n_kv_heads * (s.head_dim * KV_DTYPE_BYTES[kv_dtype]
                               + scale_b)


def decode_kv_bytes(s: Shape, lengths: Iterable[int], *, T: int,
                    kv_dtype: str = "bf16", ragged: bool = True) -> float:
    """KV-cache bytes read by one decode step's attention, whole model:
    each slot's fill depth (ragged) or the whole allocation (dense);
    windowed layers cap a slot's rows at the window."""
    lengths = [int(x) for x in lengths]
    row = _kv_row(s, kv_dtype)
    total = 0.0
    for w in s.windows:
        cap = min(T, w) if w > 0 else T
        rows = (sum(min(ln, cap) for ln in lengths) if ragged
                else len(lengths) * cap)
        total += rows * row
    return total


def paged_gather_bytes(s: Shape, lengths: Iterable[int], *, page: int,
                       T: int, kv_dtype: str = "bf16") -> Dict[str, float]:
    """The paged read: whole pages per slot and layer, plus the block
    table and the lengths the kernel prefetches."""
    lengths = [int(x) for x in lengths]
    row = _kv_row(s, kv_dtype)
    kv_total = 0.0
    for w in s.windows:
        cap = min(T, w) if w > 0 else T
        kv_total += sum(-(-min(ln, cap) // page) * page
                        for ln in lengths) * row
    B = len(lengths)
    table = 4.0 * B * -(-T // page) + 4.0 * B
    exact = decode_kv_bytes(s, lengths, T=T, kv_dtype=kv_dtype)
    total = kv_total + table
    return {"kv_bytes": kv_total, "table_bytes": table, "total": total,
            "overhead_frac": total / exact if exact > 0 else 0.0}


# ---------------------------------------------------------------- kernels

def attn_kernel_cost(s: Shape, lengths: Sequence[int], *, T: int,
                     kv_dtype: str = "bf16", page: int = 0):
    """(flops, bytes) of one layer's S=1 decode attention call over slots
    at fill depths `lengths`: the KV rows the slots need (page-granular
    for a paged pool, with its table), the query in and the output out."""
    one = Shape(**{**s.__dict__, "windows": (0,)})
    if page:
        kv = paged_gather_bytes(one, lengths, page=page, T=T,
                                kv_dtype=kv_dtype)["total"]
    else:
        kv = decode_kv_bytes(one, lengths, T=T, kv_dtype=kv_dtype)
    B = len(lengths)
    qo = 2 * B * s.n_heads * s.head_dim * ACT_BYTES + 4 * B
    flops = 4.0 * s.n_heads * s.head_dim * sum(int(x) for x in lengths)
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
    """Weights every token multiplies through: attention and MLP of each
    layer, and the tied output head (K*d wide for the widened embedding,
    d wide for the recycled one). The input lookup is not a matmul."""
    d, h, hk, dh = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim
    layer = d * h * dh * 2 + d * hk * dh * 2 + 3 * d * s.d_ff
    head = s.vocab * (d if s.recycled else s.altup_k * d)
    return s.n_layers * layer + head


def span_flops(s: Shape, start: int, n: int) -> float:
    """Model FLOPs of n consecutive tokens at 0-based positions
    start..start+n-1: 2 per matmul weight, causal attention over
    position + 1 keys in each layer (4 H dh per key: scores and values),
    and AltUp's predict and correct (2K^2 d + 3K d per layer)."""
    K, d = s.altup_k, s.d_model
    altup = s.n_layers * (2.0 * K * K * d + 3.0 * K * d) if K > 1 else 0.0
    keys = 0.0
    for w in s.windows:
        if w > 0:
            keys += sum(min(start + i + 1, w) for i in range(n))
        else:
            keys += n * start + n * (n + 1) / 2
    return n * (2.0 * matmul_params(s) + altup) \
        + 4.0 * s.n_heads * s.head_dim * keys
