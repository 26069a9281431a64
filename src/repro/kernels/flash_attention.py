"""Blocked online-softmax (Flash) attention Pallas TPU kernel.

TPU adaptation of the FlashAttention insight (IO-aware tiling): q/k/v
stream through VMEM in (block_q x d) / (block_k x d) tiles sized for the
MXU (128-aligned); the softmax running max/denominator and the output
accumulator live in VMEM scratch across the kv-block grid dimension
(TPU Pallas expresses the kv loop as the innermost "arbitrary" grid axis
revisiting the same output block, rather than a CUDA-style inner loop).

Supports causal masking and sliding windows (gemma-style local layers).
Fully-masked kv blocks are SKIPPED, not computed-and-masked: for a causal
grid, kv blocks strictly above the diagonal, and for a sliding window,
kv blocks entirely older than `window`, (a) predicate their compute off
with `pl.when` and (b) remap their k/v block fetch to the q-block's
diagonal block through the index map — the TPU pipeline emitter elides
copies whose block indices did not change, so skipped blocks cost neither
FLOPs nor HBM reads. Outputs are identical to the masked full grid
(tested in tests/test_kernels.py).

Quantized K/V (the prefill side of the quantized KV-cache serving path,
cfg.kv_cache_dtype = int8 | fp8): 1-byte codes plus per-row f32 scales
`k_scale`/`v_scale` (BH, T) ride along as two extra refs through the same
skip-remapped index map, and `code * scale` is fused into the kv-tile
load in VMEM — dequantized K/V are never materialized in HBM, and a
skipped block skips its scale fetch too.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_skipped(qi, ki, *, causal: bool, window: int,
                   block_q: int, block_k: int):
    """True when kv block ki is FULLY masked for q block qi. Shared by the
    kernel's compute predicate and the index-map fetch clamp so the two
    can never disagree."""
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    skip = jnp.zeros((), jnp.bool_)
    if causal:
        skip = skip | (k_lo > q_hi)          # strictly above the diagonal
    if window > 0:
        skip = skip | (q_lo - k_hi >= window)  # entirely older than window
    return skip


def _fa_kernel(q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
               window: int, block_q: int, block_k: int, nk: int,
               quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = jnp.logical_not(_block_skipped(qi, ki, causal=causal,
                                         window=window, block_q=block_q,
                                         block_k=block_k))

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # fused dequant: codes * per-row scale, in VMEM
            k = k * ks_ref[0].T
            v = v * vs_ref[0].T
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (q_pos >= k_pos)
        if window > 0:
            mask = mask & (q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                       # (bq, 1)
        m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))
        alpha = jnp.exp(m_prev[:, 0] - m_new)
        pexp = jnp.exp(s - m_new[:, None])
        pexp = jnp.where(mask, pexp, 0.0)
        l_new = alpha * l_scr[:, 0] + pexp.sum(axis=-1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot(pexp, v)
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _fa_paged_kernel(bt_ref, *rest, **kw):
    # the block table only steers the index maps; the compute body is the
    # contiguous kernel on logical block positions, unchanged
    _fa_kernel(*rest, **kw)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None,
                    block_table: jax.Array | None = None,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q, k, v: (BH, S, dh) — GQA head expansion happens in ops.py.
    k_scale/v_scale: optional (BH, T) f32 per-row dequant scales for
    quantized (int8/fp8-code) k/v — dequant is fused into the kv-tile
    load.

    block_table: optional (BH, nk) int32 — PAGED mode. k/v are then
    BLOCK POOLS (NB, bk, dh) shared across rows (bk = k.shape[1], the
    page size), scales (NB, bk), and row b's logical kv block j lives at
    pool block block_table[b, j]. The table rides as a scalar-prefetch
    operand and the kv index map composes the lookup with the existing
    skip remap: a skipped block re-fetches the diagonal block's PHYSICAL
    page, so the elided-copy trick (no HBM reads for masked blocks)
    survives paging. Compute/masking runs on logical positions and is
    identical to the contiguous kernel on the gathered rows; with
    causal=True, garbage rows in the tail pages (logical position >= S)
    are masked/skipped exactly like padded contiguous rows.

    Returns (BH, S, dh). interpret=None auto-detects from the backend
    (compiled on TPU, interpreted on CPU).
    """
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), \
        "pass both k_scale and v_scale, or neither"
    paged = block_table is not None
    BH, S, dh = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = min(block_q, S)
    assert S % bq == 0
    nq = S // bq
    if paged:
        bk = k.shape[1]                      # pool blocks ARE the pages
        nk = block_table.shape[1]
        assert causal or window > 0, \
            "paged flash needs causal/window masking to cover tail pages"
    else:
        T = k.shape[1]
        bk = min(block_k, T)
        assert T % bk == 0
        nk = T // bk
    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             window=window, block_q=bq, block_k=bk, nk=nk,
                             quantized=quantized)

    def _logical_j(i, j):
        # remap skipped blocks' fetch to q-block i's diagonal kv block
        # (always unskipped): the repeated index elides the copy on TPU
        if not (causal or window > 0):
            return j
        skip = _block_skipped(i, j, causal=causal, window=window,
                              block_q=bq, block_k=bk)
        return jnp.where(skip, (i * bq) // bk, j)

    if paged:
        def kv_map(b, i, j, bt):
            # skip remap composes with the table: physical page of the
            # (possibly remapped) logical block
            return (bt[b, _logical_j(i, j)], 0, 0)

        def scale_map(b, i, j, bt):
            return (bt[b, _logical_j(i, j)], 0, 0)

        q_map = lambda b, i, j, bt: (b, i, 0)
    else:
        def kv_map(b, i, j):
            return (b, _logical_j(i, j), 0)

        def scale_map(b, i, j):
            # same remap: a skipped kv block skips its scale fetch too
            return (b, 0, _logical_j(i, j))

        q_map = lambda b, i, j: (b, i, 0)

    in_specs = [
        pl.BlockSpec((1, bq, dh), q_map),
        pl.BlockSpec((1, bk, dh), kv_map),
        pl.BlockSpec((1, bk, dh), kv_map),
    ]
    operands = [q, k, v]
    if quantized:
        # scales viewed (rows, 1, T): a (1, bk) block of the 2-D (rows, T)
        # array breaks the TPU lowering's (8, 128) rule, a (1, 1, bk) one
        # does not; the kernel turns the (1, bk) row back into a column
        in_specs += [pl.BlockSpec((1, 1, bk), scale_map),
                     pl.BlockSpec((1, 1, bk), scale_map)]
        operands += [k_scale.astype(jnp.float32)[:, None],
                     v_scale.astype(jnp.float32)[:, None]]

    scratch_shapes = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, dh), jnp.float32),
    ]
    out_spec = pl.BlockSpec((1, bq, dh), q_map)
    out_shape = jax.ShapeDtypeStruct((BH, S, dh), q.dtype)
    if paged:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nq, nk),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch_shapes,
        )
        return pl.pallas_call(
            functools.partial(_fa_paged_kernel, **kern.keywords),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(block_table.astype(jnp.int32), *operands)

    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*operands)
