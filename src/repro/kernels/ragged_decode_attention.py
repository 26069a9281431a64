"""Length-aware S=1 GQA decode attention over slot caches (Pallas TPU).

Decode is memory-bandwidth-bound on the KV-cache read (Pope et al. 2022):
the dense path scores every query against the ENTIRE allocated cache
(B, T, Hk, Dh) and masks, so a slot 40 tokens deep still pays for all T
cache rows every step. This kernel takes per-slot fill depths `lengths`
(B,) and visits kv blocks only up to ceil(len_b / block_k) per slot:

* the kv-block grid axis is clamped through a scalar-prefetch index map
  (`PrefetchScalarGridSpec`), so blocks past a slot's fill depth re-map to
  the slot's last valid block — the TPU pipeline emitter elides copies
  whose indices did not change, giving ZERO HBM reads past the fill depth;
* compute for those blocks is predicated off with `pl.when`, so the
  online-softmax accumulators only ever see real rows;
* the GQA head-group expansion is fused: queries arrive grouped
  (B, Hk, rep, Dh) and each kv row is read ONCE and scored against all
  `rep` grouped queries of its head (a (rep, block_k) MXU matmul per
  head), instead of materializing rep copies of k/v like the dense jnp
  path;
* the grid is (slot, kv block): one block carries ALL Hk heads,
  (1, block_k, Hk, Dh) over the (B, T, Hk, Dh) cache, and the heads loop
  statically in VMEM. The TPU lowering needs the last two block dims
  divisible by (8, 128) or equal to the array's, so a one-head block
  (1, block_k, 1, Dh) is refused; all heads keep the cache layout as is.

Quantized slot caches (cfg.kv_cache_dtype = int8 | fp8): k/v arrive as
1-byte codes with per-row, per-head f32 scales `k_scale`/`v_scale`
(B, T, Hk) riding along as two extra refs (blocks (1, block_k, Hk))
through the SAME clamped index map, and dequantization is FUSED into the
kv-block load — `code * scale` happens in VMEM right before the MXU
matmul, so dequantized K/V are never materialized in HBM and the cache
read shrinks to ~1 byte/elem + 4 scale bytes per row-head. Block
skipping and scalar-prefetch clamping are unchanged: a skipped block
skips its scale fetch too.

Ring-buffer sliding-window caches need NO host-side roll and no in-kernel
position remap: attention is permutation-invariant over the key set once
masking is decided, and a W-slot ring at depth pos holds exactly the last
min(pos+1, W) positions in rows {i : i < min(pos+1, W)} — i.e. the
wraparound index remap collapses to the same `row < length` predicate as
the linear cache (callers pass lengths = min(pos+1, W)). Scale rows wrap
with their code rows (one shared write index), so the rule is unchanged
under quantization. See docs/kernels.md for the bytes model.

Empty slots (length 0) produce exact zeros (the engine ignores their
logits); boundary blocks of a T % block_k != 0 cache are handled by
masking the padded rows out of both the scores and the value read.

`paged_ragged_decode_attention` is the block-table variant for the paged
KV pool (serve/paging.py): k/v arrive as batchless row pools and a
second scalar-prefetch operand — the per-slot block table — relocates
each logical kv page to its physical pool page inside the index map.
Same compute body, same clamp, same zero-reads-past-fill guarantee.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, *rest, scale: float, block_k: int,
            nk: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    def head(h):
        # one kv head of the resident (bk, Hk, dh) block, static h
        q = q_ref[0, h].astype(jnp.float32)            # (rep, dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)      # (bk, dh)
        v = v_ref[0, :, h, :].astype(jnp.float32)      # (bk, dh)
        if quantized:
            # fused dequant: codes * per-row scale, in VMEM — the f32
            # k/v tiles never exist in HBM
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        # one kv read serves all `rep` grouped queries (fused GQA)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG_INF)
        # boundary blocks (T % block_k != 0) carry undefined padded rows;
        # zero them so 0-weight rows cannot poison the accumulator
        rowmask = (j * block_k
                   + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)) < length
        v = jnp.where(rowmask, v, 0.0)
        m_prev = m_scr[h]                              # (rep, 1)
        m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))
        alpha = jnp.exp(m_prev[:, 0] - m_new)
        pexp = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        l_scr[h, :, 0] = alpha * l_scr[h, :, 0] + pexp.sum(axis=-1)
        acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot(pexp, v)
        m_scr[h, :, 0] = m_new

    @pl.when(j * block_k < length)
    def _body():
        for h in range(q_ref.shape[1]):
            head(h)

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _scratch(Hk: int, rep: int, dh: int):
    # per-head online-softmax state: running max, denominator, accumulator
    return [pltpu.VMEM((Hk, rep, 1), jnp.float32),
            pltpu.VMEM((Hk, rep, 1), jnp.float32),
            pltpu.VMEM((Hk, rep, dh), jnp.float32)]


def _paged_kernel(len_ref, bt_ref, *rest, scale: float, block_k: int,
                  nk: int, quantized: bool):
    # the block table is consumed entirely by the index maps; the compute
    # body is the contiguous kernel unchanged (logical positions j*page+i
    # are what the fill-depth mask needs, and the grid hands it logical j)
    del bt_ref
    _kernel(len_ref, *rest, scale=scale, block_k=block_k, nk=nk,
            quantized=quantized)


def paged_ragged_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                  lengths: jax.Array,
                                  block_table: jax.Array, *,
                                  page: int, t_max: int,
                                  k_scale: jax.Array | None = None,
                                  v_scale: jax.Array | None = None,
                                  scale: float | None = None,
                                  interpret: bool | None = None) -> jax.Array:
    """Block-table variant: k, v are ROW POOLS (R, Hk, Dh) shared by all
    slots (R = n_pages * page rows), and each slot's cache is the page
    sequence named by its block-table row. q: (B, Hk, rep, Dh) grouped
    queries; lengths: (B,) fill depths; block_table: (B, npages) int32
    physical-page ids for logical pages 0..npages-1 (entries past a
    slot's fill are garbage and never fetched). k_scale/v_scale: optional
    (R, Hk) f32 pool scales. t_max: static logical read bound (the kv
    bucket) — the kv grid covers cdiv(t_max, page) logical pages.

    The pool is viewed as (n_pages, page, Hk, Dh) and the kv index map
    composes the block-table lookup with the SAME last-needed-block clamp
    as the contiguous kernel: grid step j fetches physical page
    block_table[b, min(j, last_b)], so steps past a slot's fill depth
    re-fetch the page already resident in VMEM (elided copy — the
    zero-reads-past-fill guarantee survives paging). Compute/masking is
    `_kernel` verbatim on logical positions, so outputs are identical to
    the contiguous kernel on the gathered rows. block_k == page (one
    page per grid step)."""
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), \
        "pass both k_scale and v_scale, or neither"
    B, Hk, rep, dh = q.shape
    R = k.shape[0]
    assert R % page == 0, (R, page)
    n_pages = R // page
    kp = k.reshape(n_pages, page, Hk, dh)
    vp = v.reshape(n_pages, page, Hk, dh)
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    nk = pl.cdiv(t_max, page)
    assert nk <= block_table.shape[1], (t_max, page, block_table.shape)
    lengths = lengths.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)

    def kv_map(b, j, lens, bt):
        # same clamp as the contiguous kernel, then through the table:
        # past-fill grid steps re-fetch a resident page (elided copy)
        last = jnp.maximum(pl.cdiv(lens[b], page) - 1, 0)
        return (bt[b, jnp.minimum(j, last)], 0, 0, 0)

    def scale_map(b, j, lens, bt):
        last = jnp.maximum(pl.cdiv(lens[b], page) - 1, 0)
        return (bt[b, jnp.minimum(j, last)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hk, rep, dh), lambda b, j, lens, bt: (b, 0, 0, 0)),
        pl.BlockSpec((1, page, Hk, dh), kv_map),
        pl.BlockSpec((1, page, Hk, dh), kv_map),
    ]
    operands = [q, kp, vp]
    if quantized:
        in_specs += [pl.BlockSpec((1, page, Hk), scale_map),
                     pl.BlockSpec((1, page, Hk), scale_map)]
        operands += [k_scale.reshape(n_pages, page, Hk).astype(jnp.float32),
                     v_scale.reshape(n_pages, page, Hk).astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hk, rep, dh),
                               lambda b, j, lens, bt: (b, 0, 0, 0)),
        scratch_shapes=_scratch(Hk, rep, dh),
    )
    kern = functools.partial(_paged_kernel, scale=scale, block_k=page,
                             nk=nk, quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, rep, dh), q.dtype),
        interpret=interpret,
        name="paged_ragged_decode_attention",
    )(lengths, block_table, *operands)


def ragged_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            lengths: jax.Array, *,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None,
                            scale: float | None = None, block_k: int = 128,
                            interpret: bool | None = None) -> jax.Array:
    """q: (B, Hk, rep, Dh) grouped queries; k, v: (B, T, Hk, Dh) slot
    caches; lengths: (B,) int32 valid-row counts (<= T). k_scale/v_scale:
    optional (B, T, Hk) f32 per-row-head dequant scales for quantized
    (int8/fp8-code) caches — dequant is fused into the kv-block load.
    Returns (B, Hk, rep, Dh). interpret=None auto-detects from the
    backend (compiled on TPU, interpreted on CPU)."""
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), \
        "pass both k_scale and v_scale, or neither"
    B, Hk, rep, dh = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bk = min(block_k, T)
    nk = pl.cdiv(T, bk)
    lengths = lengths.astype(jnp.int32)

    def kv_map(b, j, lens):
        # clamp to the slot's last needed block: past-fill grid steps
        # re-fetch an already-resident block (elided copy -> no HBM read)
        last = jnp.maximum(pl.cdiv(lens[b], bk) - 1, 0)
        return (b, jnp.minimum(j, last), 0, 0)

    def scale_map(b, j, lens):
        # same clamp as kv_map: a skipped kv block skips its scales too
        last = jnp.maximum(pl.cdiv(lens[b], bk) - 1, 0)
        return (b, jnp.minimum(j, last), 0)

    in_specs = [
        pl.BlockSpec((1, Hk, rep, dh), lambda b, j, lens: (b, 0, 0, 0)),
        pl.BlockSpec((1, bk, Hk, dh), kv_map),
        pl.BlockSpec((1, bk, Hk, dh), kv_map),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk, Hk), scale_map),
                     pl.BlockSpec((1, bk, Hk), scale_map)]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hk, rep, dh),
                               lambda b, j, lens: (b, 0, 0, 0)),
        scratch_shapes=_scratch(Hk, rep, dh),
    )
    kern = functools.partial(_kernel, scale=scale, block_k=bk, nk=nk,
                             quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, rep, dh), q.dtype),
        interpret=interpret,
        name="ragged_decode_attention",
    )(lengths, *operands)
