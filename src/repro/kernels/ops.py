"""Jitted public wrappers around the Pallas kernels.

These are the entry points the model layers call. Interpret mode is left
to the kernel entry points, which resolve `interpret=None` from the
backend when the wrapper is traced (compiled on TPU, interpreted on
CPU); nothing here looks at the backend while the module is imported.
The interpreted kernels are validated against ref.py by the test suite.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import (altup_fused, flash_attention,
                           ragged_decode_attention as ragged_mod,
                           rwkv6_scan)


@partial(jax.jit, static_argnames=("block_t", "block_d"))
def altup_predict_correct(x_wide, x_tilde, sel, p, g, *, block_t=256,
                          block_d=512):
    """Shape-polymorphic wrapper: (..., K, d) stream + (..., d) computed
    block -> fused predict+correct. Leading axes are flattened to T."""
    lead = x_wide.shape[:-2]
    K, d = x_wide.shape[-2:]
    T = 1
    for n in lead:
        T *= n
    bt = block_t
    while T % bt and bt > 1:
        bt //= 2
    bd = block_d
    while d % bd and bd > 1:
        bd //= 2
    out = altup_fused.altup_predict_correct(
        x_wide.reshape(T, K, d), x_tilde.reshape(T, d), sel, p, g,
        block_t=bt, block_d=bd)
    return out.reshape(*lead, K, d)


def decode_altup_predict_correct(x_wide, x_tilde, sel, p, g):
    """Batched single-token AltUp predict+correct for the decode loop.

    x_wide: (B, S, K, d) widened stream (S is 1 for decode ticks, the
    chunk size during chunked prefill); x_tilde: (B, S, d). One fused
    VMEM pass instead of the 2-3 separate HBM passes the unfused
    predict/correct einsums make per decode step. Decode batches are
    small, so blocks are sized for the flattened B*S token axis.
    """
    B = x_wide.shape[0] * x_wide.shape[1]
    return altup_predict_correct(x_wide, x_tilde, sel, p, g,
                                 block_t=min(64, B), block_d=512)


@partial(jax.jit, static_argnames=("block_k",))
def ragged_decode_attn(q, k, v, lengths, k_scale=None, v_scale=None, *,
                       block_k=128):
    """Length-aware S=1 GQA decode attention over slot caches.

    q: (B, 1, H, dh) single-token queries; k, v: (B, T, Hk, dh) slot
    caches; lengths: (B,) per-slot valid-row counts. Heads are grouped
    (B, Hk, rep, dh) — matching sdpa's GQA layout — so each cache row is
    read once per kv head, not once per query head. k_scale/v_scale:
    optional (B, T, Hk) f32 per-row-head scales for quantized (int8/fp8)
    slot caches — dequant fuses into the kv-block load inside the kernel.
    Returns (B, 1, H, dh).
    """
    B, S, H, dh = q.shape
    assert S == 1, "ragged decode kernel is single-token (S=1) only"
    Hk = k.shape[2]
    rep = H // Hk
    qg = q[:, 0].reshape(B, Hk, rep, dh)
    o = ragged_mod.ragged_decode_attention(qg, k, v, lengths,
                                           k_scale=k_scale,
                                           v_scale=v_scale,
                                           block_k=block_k)
    return o.reshape(B, 1, H, dh)


@partial(jax.jit, static_argnames=("page", "t_max", "block_k"))
def paged_ragged_decode_attn(q, k_pool, v_pool, lengths, block_table,
                             k_scale=None, v_scale=None, *, page, t_max,
                             block_k=None):
    """Paged-pool variant of `ragged_decode_attn`.

    q: (B, 1, H, dh) single-token queries; k_pool/v_pool: (R, Hk, dh)
    batchless row pools (R = n_pages * page); block_table: (B, npages)
    int32 physical-page ids per logical page; lengths: (B,) fill depths.
    k_scale/v_scale: optional (R, Hk) f32 pool scales (quantized caches).
    t_max: static logical read bound (the kv bucket). The kernel indexes
    KV pages through the block table in its scalar-prefetch index map —
    no gathered copy of the cache is ever materialized. block_k is
    accepted for signature parity and ignored (the page is the block).
    Returns (B, 1, H, dh).
    """
    del block_k
    B, S, H, dh = q.shape
    assert S == 1, "paged ragged decode kernel is single-token (S=1) only"
    Hk = k_pool.shape[1]
    rep = H // Hk
    qg = q[:, 0].reshape(B, Hk, rep, dh)
    o = ragged_mod.paged_ragged_decode_attention(
        qg, k_pool, v_pool, lengths, block_table, page=page, t_max=t_max,
        k_scale=k_scale, v_scale=v_scale)
    return o.reshape(B, 1, H, dh)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def mha_flash(q, k, v, k_scale=None, v_scale=None, *, causal=True,
              window=0, block_q=128, block_k=128):
    """q: (B, S, H, dh), k/v: (B, T, Hk, dh) with GQA expansion.
    k_scale/v_scale: optional (B, T, Hk) f32 per-row-head scales for
    quantized k/v (prefill over a quantized cache) — dequant fuses into
    the kv-tile load; scales ride through the same GQA expansion."""
    assert (k_scale is None) == (v_scale is None), \
        "pass both k_scale and v_scale, or neither"
    B, S, H, dh = q.shape
    T, Hk = k.shape[1], k.shape[2]
    rep = H // Hk
    kx = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vx = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], dh)
    scales = {}
    if k_scale is not None:
        folds = lambda s: (jnp.repeat(s, rep, axis=2) if rep > 1 else s) \
            .transpose(0, 2, 1).reshape(B * H, T)
        scales = {"k_scale": folds(k_scale), "v_scale": folds(v_scale)}
    o = flash_attention.flash_attention(
        fold(q), fold(kx), fold(vx), causal=causal, window=window,
        block_q=block_q, block_k=block_k, **scales)
    return o.reshape(B, H, S, dh).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("page", "causal", "window", "block_q"))
def mha_flash_paged(q, k_pool, v_pool, block_table, k_scale=None,
                    v_scale=None, *, page, causal=True, window=0,
                    block_q=128):
    """Flash attention over a PAGED kv pool (prefill/verify reads).

    q: (B, S, H, dh); k_pool/v_pool: (R, Hk, dh) batchless row pools
    (R = n_pages * page); block_table: (B, npages) physical-page ids;
    k_scale/v_scale: optional (R, Hk) f32 pool scales. The pool is viewed
    per kv head as page blocks and only the BLOCK TABLE is expanded for
    the GQA head fold — k/v codes are never repeated or gathered in HBM;
    the kernel's index map reads each physical page directly. Requires
    causal masking (garbage tail-page rows at logical positions >= the
    valid count are masked/skipped like padded contiguous rows) and the
    caller guarantees logical row t is valid iff t < S.
    Returns (B, S, H, dh).
    """
    assert (k_scale is None) == (v_scale is None), \
        "pass both k_scale and v_scale, or neither"
    B, S, H, dh = q.shape
    R, Hk = k_pool.shape[0], k_pool.shape[1]
    rep = H // Hk
    NP = R // page
    # (R, Hk, dh) -> per-kv-head page blocks (Hk*NP, page, dh): head h's
    # copy of physical page p is pool block h*NP + p — pure reshape views,
    # no data duplication beyond the transpose
    pool = lambda t: (t.reshape(NP, page, Hk, dh)
                      .transpose(2, 0, 1, 3).reshape(Hk * NP, page, dh))
    nk = block_table.shape[1]
    # folded row b*H + h (ops.mha_flash fold order) reads kv head h//rep
    kvh = jnp.arange(H) // rep                             # (H,)
    btf = (kvh[None, :, None] * NP
           + block_table.astype(jnp.int32)[:, None, :]).reshape(B * H, nk)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], dh)
    scales = {}
    if k_scale is not None:
        pools = lambda s: (s.reshape(NP, page, Hk).transpose(2, 0, 1)
                           .reshape(Hk * NP, page).astype(jnp.float32))
        scales = {"k_scale": pools(k_scale), "v_scale": pools(v_scale)}
    o = flash_attention.flash_attention(
        fold(q), pool(k_pool), pool(v_pool), block_table=btf,
        causal=causal, window=window, block_q=block_q, **scales)
    return o.reshape(B, H, S, dh).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_wkv(r, k, v, w, u, *, chunk=128):
    """r,k,v,w: (B, S, H, Dh); u: (H, Dh). Returns out + final state
    (B, H, Dh, Dh)."""
    B, S, H, Dh = r.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    ub = jnp.broadcast_to(u[None], (B, H, Dh)).reshape(B * H, Dh)
    out, s = rwkv6_scan.rwkv6_wkv(fold(r), fold(k), fold(v), fold(w), ub,
                                  chunk=chunk)
    return (out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3),
            s.reshape(B, H, Dh, Dh))
