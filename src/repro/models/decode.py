"""Single-token decode (serve_step) with per-layer caches.

Cache layout mirrors the segment plan: one pytree per segment, stacked over
the segment's layers so the decode layer loop is the same lax.scan as
training. KV caches (attn, mla) ride in the scan CARRY with the layer
index: each layer writes only its new rows into the stack and reads its
layer where it lies, so a step moves the new rows and the rows it reads,
never a per-layer slice or a rebuilt copy of the stack. Recurrent state
(rwkv, mamba) is rewritten whole every step and rides as scan xs/ys.

Cache kinds:
  attn : k, v      (n, B, T, Hk, Dh)   post-RoPE keys
  mla  : latent    (n, B, T, r+qr)     compressed latents (head-free!)
  rwkv : wkv       (n, B, H, Dh, Dh) + token-shift tails (n, B, d)
  mamba: ssm       (n, B, H, Dh, N)  + conv tail (n, B, Kw-1, Cc)
  shared_attn: k,v (B, T, Hk, Dh)      per shared-block invocation

Slot-based serving: `pos` is either a scalar (uniform static batch) or a
per-slot (B,) vector, so B sequences at different depths decode in ONE
jitted step (continuous batching; see serve/scheduler.py). Per-slot
attention masking falls out of the existing q_pos/k_pos machinery in
layers.sdpa. Sliding-window segments allocate a RING cache of length
min(T, window): writes wrap at pos % W and key positions are reconstructed
from the write cursor, so long-context decode memory is O(window), not
O(T), for local layers.

Decode hot path (see docs/kernels.md): steps may carry S > 1 tokens per
slot (chunked prefill; padded tokens suppressed via `n_valid` through
out-of-bounds-dropped cache writes), cache READS are sliced to the static
`kv_len` bucket the engine derives from the deepest active slot (O(len)
bytes, not O(T)), and on TPU S=1 attention routes through the ragged
Pallas decode kernel (kernels/ragged_decode_attention.py) with the fused
AltUp predict/correct kernel in the layer loop — both with dense jnp
fallbacks that are their test oracles.

Quantized slot caches (cfg.kv_cache_dtype = int8 | fp8, see
kernels/quant.py): attention k/v caches and MLA latent caches store
1-byte codes plus per-head, per-position f32 scales as sibling cache
leaves ("k_scale"/"v_scale" (n, B, T, Hk), "latent_scale" (n, B, T)).
Quantize-on-write happens HERE — k_new/v_new are rounded as they land
(including each chunked-prefill chunk), codes and scales share one write
index so ring wraparound and padded-token drops stay in lockstep — and
dequantization is fused inside the Pallas decode kernels (the dense
fallback dequantizes in layers.attention_block and is the oracle).
Recurrent state (rwkv/mamba) always stays float: it is re-read and
re-written every step, so low-bit storage would accumulate rounding.

A note on AltUp economics (paper Sec. 3.2): caches are built from the
ACTIVE d-wide sub-block only, so the widened (K*d) stream adds ZERO bytes
to the KV cache — decode memory is identical to the unwidened model.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core import altup as alt
from repro.kernels import quant
from repro.models import layers as L
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models import moe as moe_lib
from repro.models.transformer import (Segment, act_dtype, batch_axes,
                                      layer_plan, _shard,
                                      unembed, embed_tokens)


def kv_quant_spec(cfg: ModelConfig) -> quant.KVQuantSpec:
    """Resolved cfg.kv_cache_dtype for the decode slot caches ("auto" =
    the activation dtype — bit-identical to the unquantized path)."""
    return quant.resolve_kv_spec(cfg.kv_cache_dtype, act_dtype(cfg))


def init_cache(cfg: ModelConfig, B: int, T: int,
               dtype=None) -> Dict[str, Any]:
    """Zero caches for a max sequence length T.

    Quantized modes (kv_cache_dtype int8/fp8) store attention k/v and MLA
    latents as low-bit codes with sibling f32 scale leaves: k/v scales
    are per (position, kv-head) — one scale per cached head-row — and
    latent scales are per position (the latent is head-free). Cross-
    attention caches (encdec) stay float: they are written once at
    prefill and the continuous-batching path never serves encdec."""
    spec = kv_quant_spec(cfg)
    # ad: recurrent/conv/shift state (always float — see module doc);
    # kd: the k/v/latent storage cfg.kv_cache_dtype selects
    ad = dtype or act_dtype(cfg)
    kd = spec.store_dtype if spec.quantized else (dtype or spec.store_dtype)
    d, dh = cfg.d_model, cfg.resolved_head_dim
    hk = cfg.n_kv_heads
    caches: Dict[str, Any] = {}
    for si, seg in enumerate(layer_plan(cfg)):
        n = seg.n
        if seg.kind == "attn":
            # sliding-window segments need only the last `window` keys:
            # ring buffer (wraparound handled in decode_attn)
            Tc = min(T, seg.window) if seg.window > 0 else T
            c = {"k": jnp.zeros((n, B, Tc, hk, dh), kd),
                 "v": jnp.zeros((n, B, Tc, hk, dh), kd)}
            if spec.quantized:
                c["k_scale"] = jnp.zeros((n, B, Tc, hk), jnp.float32)
                c["v_scale"] = jnp.zeros((n, B, Tc, hk), jnp.float32)
        elif seg.kind == "shared_attn":
            c = {"k": jnp.zeros((B, T, hk, dh), kd),
                 "v": jnp.zeros((B, T, hk, dh), kd)}
            if spec.quantized:
                c["k_scale"] = jnp.zeros((B, T, hk), jnp.float32)
                c["v_scale"] = jnp.zeros((B, T, hk), jnp.float32)
        elif seg.kind == "mla":
            w = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            c = {"latent": jnp.zeros((n, B, T, w), kd)}
            if spec.quantized:
                c["latent_scale"] = jnp.zeros((n, B, T), jnp.float32)
        elif seg.kind == "rwkv":
            H = d // cfg.rwkv.head_dim
            hd = cfg.rwkv.head_dim
            c = {"wkv": jnp.zeros((n, B, H, hd, hd), jnp.float32),
                 "shift_tm": jnp.zeros((n, B, d), ad),
                 "shift_cm": jnp.zeros((n, B, d), ad)}
        elif seg.kind == "mamba":
            s = cfg.ssm
            d_in = s.expand * d
            H = d_in // s.head_dim
            cc = d_in + 2 * s.d_state
            c = {"ssm": jnp.zeros((n, B, H, s.head_dim, s.d_state),
                                  jnp.float32),
                 "conv": jnp.zeros((n, B, s.d_conv - 1, cc), ad)}
        else:
            raise ValueError(seg.kind)
        caches[f"seg{si}"] = c
    if cfg.family == "encdec":
        # cross-attention K/V over the (fixed) encoder output, one per
        # decoder layer — filled once at prefill.
        caches["cross"] = {
            "k": jnp.zeros((cfg.n_layers, B, cfg.encoder_seq, hk, dh), ad),
            "v": jnp.zeros((cfg.n_layers, B, cfg.encoder_seq, hk, dh), ad)}
    return caches


def init_paged_cache(cfg: ModelConfig, B: int, T: int, *, n_pages: int,
                     page: int, dtype=None) -> Dict[str, Any]:
    """Zero PAGED caches: row-indexed leaves (attention k/v + scales, MLA
    latents) drop the per-slot batch axis and pool all rows — R =
    n_pages * page physical rows shared by every slot through a
    (B, ceil(T/page)) block table (serve/paging.PagePool). A page is
    `page` CONTIGUOUS pool rows; logical row q of a slot lives at
    physical row bt[b, q // page] * page + q % page, and quantized scale
    leaves ride the same physical rows so codes + scales stay in page
    lockstep for free. Ring segments use the same pool through the same
    table (only logical rows < min(window, table capacity) are ever
    touched). Recurrent state (rwkv/mamba + token-shift/conv tails)
    stays per-slot (n, B, ...): it is O(1) per request, not O(T).
    encdec is never served through the paged path."""
    assert cfg.family != "encdec", "paged caches serve decoder-only models"
    spec = kv_quant_spec(cfg)
    ad = dtype or act_dtype(cfg)
    kd = spec.store_dtype if spec.quantized else (dtype or spec.store_dtype)
    d, dh = cfg.d_model, cfg.resolved_head_dim
    hk = cfg.n_kv_heads
    R = n_pages * page
    caches: Dict[str, Any] = {}
    for si, seg in enumerate(layer_plan(cfg)):
        n = seg.n
        if seg.kind == "attn":
            c = {"k": jnp.zeros((n, R, hk, dh), kd),
                 "v": jnp.zeros((n, R, hk, dh), kd)}
            if spec.quantized:
                c["k_scale"] = jnp.zeros((n, R, hk), jnp.float32)
                c["v_scale"] = jnp.zeros((n, R, hk), jnp.float32)
        elif seg.kind == "shared_attn":
            c = {"k": jnp.zeros((R, hk, dh), kd),
                 "v": jnp.zeros((R, hk, dh), kd)}
            if spec.quantized:
                c["k_scale"] = jnp.zeros((R, hk), jnp.float32)
                c["v_scale"] = jnp.zeros((R, hk), jnp.float32)
        elif seg.kind == "mla":
            w = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            c = {"latent": jnp.zeros((n, R, w), kd)}
            if spec.quantized:
                c["latent_scale"] = jnp.zeros((n, R), jnp.float32)
        elif seg.kind == "rwkv":
            H = d // cfg.rwkv.head_dim
            hd = cfg.rwkv.head_dim
            c = {"wkv": jnp.zeros((n, B, H, hd, hd), jnp.float32),
                 "shift_tm": jnp.zeros((n, B, d), ad),
                 "shift_cm": jnp.zeros((n, B, d), ad)}
        elif seg.kind == "mamba":
            s = cfg.ssm
            d_in = s.expand * d
            H = d_in // s.head_dim
            cc = d_in + 2 * s.d_state
            c = {"ssm": jnp.zeros((n, B, H, s.head_dim, s.d_state),
                                  jnp.float32),
                 "conv": jnp.zeros((n, B, s.d_conv - 1, cc), ad)}
        else:
            raise ValueError(seg.kind)
        caches[f"seg{si}"] = c
    return caches


def _paged_row_axis(name: str, ndim: int) -> Optional[int]:
    """Physical-row axis of a PAGED cache leaf, None for non-row leaves.
    Paged k/v are (n, R, hk, dh) stacked | (R, hk, dh) shared; scales
    (n, R, hk) | (R, hk); latents (n, R, w) + (n, R). Recurrent leaves
    keep their per-slot layout and are not row-pooled."""
    if name in ("k", "v"):
        return 1 if ndim == 4 else 0
    if name in ("k_scale", "v_scale"):
        return 1 if ndim == 3 else 0
    if name in ("latent", "latent_scale"):
        return 1
    return None


def _leaf_name(path) -> str:
    return path[-1].key if hasattr(path[-1], "key") else str(path[-1])


def _paged_rows(block_table, widx, page: int, Tc: int, R: int):
    """Translate logical write rows through the block table.

    widx: (B|1, S) logical rows from decode_positions, with the
    padded-token drop marker == Tc. Returns (B, S) PHYSICAL pool rows;
    dropped writes map to R (out of range, killed by mode="drop"). The
    lookup page is clamped to Tc - 1 first so the drop marker itself
    cannot index past the block table when Tc is page-aligned."""
    bt = block_table
    B = bt.shape[0]
    w = jnp.asarray(widx, jnp.int32)
    if w.shape[0] == 1 and B > 1:
        w = jnp.broadcast_to(w, (B,) + w.shape[1:])
    lp = jnp.minimum(w, Tc - 1) // page
    phys = jnp.take_along_axis(bt, lp, axis=1) * page + w % page
    return jnp.where(w >= Tc, R, phys)


def _gather_rows(block_table, page: int, Tb: int):
    """(B, Tb) physical pool rows backing logical rows 0..Tb-1 of every
    slot. The gather preserves logical row order, so a paged read slice
    is bitwise-identical to the contiguous cache_k[:, :Tb] slice —
    unassigned table entries alias page 0, whose rows are masked (or
    write-dropped) exactly like unwritten contiguous rows."""
    rows = jnp.arange(Tb, dtype=jnp.int32)
    return block_table[:, rows // page] * page + rows % page


def cache_pspecs(cfg: ModelConfig, caches, mesh) -> Any:
    """PartitionSpecs for the cache pytree: shard kv-heads over `model` when
    divisible, otherwise shard the long sequence axis over ("data","model")
    — the sequence-parallel cache layout used for long-context decode."""
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    nb = _nb(mesh)
    bax = batch_axes(mesh)

    def spec_for(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v"):
            batch_dim = 1 if leaf.ndim == 5 else 0   # stacked vs shared blk
            lead = (None,) * batch_dim
            B, T, hk = leaf.shape[batch_dim:batch_dim + 3]
            b_ok = B % nb == 0
            if b_ok and hk % msize == 0:
                return P(*lead, bax, None, "model", None)
            if b_ok and T % msize == 0:   # kv heads unshardable: seq/model
                return P(*lead, bax, "model", None, None)
            if b_ok:                      # (e.g. whisper 1500-frame cross)
                return P(*lead, bax, None, None, None)
            # tiny batch (long-context): sequence-parallel cache
            return P(*lead, None, ("data", "model"), None, None)
        if name in ("k_scale", "v_scale"):
            # quantized-cache scales mirror their code leaves minus the
            # head-dim axis: (n, B, T, hk) stacked | (B, T, hk) shared
            batch_dim = 1 if leaf.ndim == 4 else 0
            lead = (None,) * batch_dim
            B, T, hk = leaf.shape[batch_dim:batch_dim + 3]
            b_ok = B % nb == 0
            if b_ok and hk % msize == 0:
                return P(*lead, bax, None, "model")
            if b_ok and T % msize == 0:
                return P(*lead, bax, "model", None)
            if b_ok:
                return P(*lead, bax, None, None)
            return P(*lead, None, ("data", "model"), None)
        if name == "latent":                          # (n, B, T, w)
            if leaf.shape[1] % nb == 0:
                return P(None, bax, "model", None)
            return P(None, None, ("data", "model"), None)
        if name == "latent_scale":                    # (n, B, T)
            if leaf.shape[1] % nb == 0:
                return P(None, bax, "model")
            return P(None, None, ("data", "model"))
        if name in ("wkv", "ssm"):                    # (n, B, H, ., .)
            b_ok = leaf.shape[1] % nb == 0
            h_ok = leaf.shape[2] % msize == 0
            return P(None, bax if b_ok else None,
                     "model" if h_ok else None, None, None)
        if name in ("shift_tm", "shift_cm"):          # (n, B, d)
            return P(None, bax if leaf.shape[1] % nb == 0 else None, None)
        if name == "conv":                            # (n, B, Kw-1, Cc)
            return P(None, bax if leaf.shape[1] % nb == 0 else None,
                     None, "model" if leaf.shape[3] % msize == 0 else None)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, caches)


def paged_cache_pspecs(cfg: ModelConfig, caches, mesh) -> Any:
    """PartitionSpecs for PAGED caches (init_paged_cache shapes — a
    separate function because paged stacked k/v is 4-D, colliding with
    the contiguous shared-block k/v rule in cache_pspecs). Row-pooled
    leaves have no batch axis and their rows are gathered through the
    block table (row-random), so the pool row axis stays unsharded and
    kv-heads shard over `model` when divisible. Recurrent leaves keep
    the contiguous per-slot rules."""
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    nb = _nb(mesh)
    bax = batch_axes(mesh)

    def spec_for(path, leaf):
        name = _leaf_name(path)
        ax = _paged_row_axis(name, leaf.ndim)
        if ax is not None:
            lead = (None,) * (ax + 1)                 # stack axis + rows
            if name in ("k", "v"):
                hk = leaf.shape[ax + 1]
                return P(*lead, "model" if hk % msize == 0 else None,
                         None)
            if name in ("k_scale", "v_scale"):
                hk = leaf.shape[ax + 1]
                return P(*lead, "model" if hk % msize == 0 else None)
            if name == "latent":                      # (n, R, w)
                return P(None, None, None)
            return P(None, None)                      # latent_scale
        if name in ("wkv", "ssm"):                    # (n, B, H, ., .)
            b_ok = leaf.shape[1] % nb == 0
            h_ok = leaf.shape[2] % msize == 0
            return P(None, bax if b_ok else None,
                     "model" if h_ok else None, None, None)
        if name in ("shift_tm", "shift_cm"):          # (n, B, d)
            return P(None, bax if leaf.shape[1] % nb == 0 else None, None)
        if name == "conv":                            # (n, B, Kw-1, Cc)
            return P(None, bax if leaf.shape[1] % nb == 0 else None,
                     None, "model" if leaf.shape[3] % msize == 0 else None)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, caches)


def _nb(mesh) -> int:
    """Total batch shards."""
    if mesh is None:
        return 1
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _update_at(cache, new, layer, idx):
    """cache (n, B, T, ...) stacked, new (B, S, ...) -> layer `layer`
    updated at write rows `idx`, in place: only the new rows are written.

    idx is a scalar (uniform batch: S contiguous rows starting there), or
    a per-slot (B|1, S) row matrix (continuous batching: every sequence
    writes at its own depth, ring rows pre-wrapped). Row indices >= T are
    DROPPED — chunked prefill uses this to suppress the writes of padded
    tokens past a slot's valid count."""
    idx = jnp.asarray(idx)
    if idx.ndim == 0:
        i = (layer, 0, idx) + (0,) * (cache.ndim - 3)
        return jax.lax.dynamic_update_slice(
            cache, new[None].astype(cache.dtype), i)
    B = cache.shape[1]
    if idx.shape[0] == 1 and B > 1:
        idx = jnp.broadcast_to(idx, (B,) + idx.shape[1:])
    return cache.at[layer, jnp.arange(B)[:, None], idx].set(
        new.astype(cache.dtype), mode="drop")


def _layer_rows(cache, layer, Tb: int):
    """Rows 0..Tb-1 of layer `layer` of a stacked (n, B, T, ...) leaf —
    the dense read slice, taken without slicing out the whole layer."""
    start = (layer,) + (0,) * (cache.ndim - 1)
    size = (1, cache.shape[1], Tb) + cache.shape[3:]
    return jax.lax.dynamic_slice(cache, start, size)[0]


def _q_pos(pos):
    """Normalize scalar / (B,) pos to an sdpa-ready q_pos of length S=1."""
    pos = jnp.asarray(pos)
    return pos[None] if pos.ndim == 0 else pos[:, None]   # (1,) | (B, 1)


def _ring_k_pos(pos, W: int, far_offset: int = 1):
    """Absolute key positions held by a W-slot ring cache at depth `pos`.

    Ring index i holds the latest absolute position p <= pos with
    p % W == i, i.e. pos - ((pos - i) mod W). Never-written slots map to
    negative positions; they are pushed to pos + far_offset so the causal
    mask kills them (their content is stale/zero). far_offset must exceed
    the largest query offset relative to `pos` — 1 for the S=1 decode
    tick; the chunked-verify path passes S + 1 so a never-written row can
    never collide with a chunk query position."""
    p = _q_pos(pos)
    if p.ndim == 1:                                       # scalar pos
        p = p[None]                                       # (1, 1)
    idx = jnp.arange(W)[None, :]                          # (1, W)
    k_abs = p - ((p - idx) % W)                           # (B|1, W)
    return jnp.where(k_abs < 0, p + far_offset, k_abs)


def _bucketed(T: int, kv_len) -> int:
    """Static read-slice length: the engine's kv-len bucket clamped to the
    cache capacity. None = no bucketing (read the whole cache)."""
    return T if kv_len is None else min(int(kv_len), T)


def decode_positions(pos, S: int, Tc: int, ring: bool, *, n_valid=None,
                     kv_len=None):
    """Hoisted per-segment position/index construction (§Perf satellite).

    Built ONCE per segment — OUTSIDE the scanned layer body — so the
    q_pos/k_pos/write-row machinery is loop-invariant across the
    segment's layers instead of being re-derived per layer per step, and
    the single `widx` is shared by the k and v cache writes.

      q_pos   (B|1, S)  absolute query positions pos + i
      widx    scalar | (B|1, S) cache write rows (ring-wrapped); padded
              tokens (i >= n_valid) remap to Tc -> dropped by _update_at
      k_pos   (Tb,) | (B|1, Tb) absolute key positions of the read slice
      lengths (B?,) valid cache rows after this step's writes (the ragged
              kernel's per-slot fill depths; ring windows collapse to the
              same `row < length` rule — see kernels/ragged_decode_attention)
      Tb      static read-slice length (kv-len bucket clamped to Tc)
    """
    pos = jnp.asarray(pos)
    Tb = _bucketed(Tc, kv_len)
    offs = jnp.arange(S)
    scalar = pos.ndim == 0
    assert not (scalar and n_valid is not None), \
        "per-slot n_valid requires a per-slot (B,) pos"
    p = pos[None] if scalar else pos                      # (1,) | (B,)
    n = jnp.full(p.shape, S, jnp.int32) if n_valid is None \
        else n_valid.astype(jnp.int32)
    q_pos = p[:, None] + offs[None]                       # (B|1, S)
    lengths = jnp.minimum(p + n, Tc).astype(jnp.int32)    # (B|1,)
    if ring:
        # ring rows wrap at Tc; ragged masking needs no wraparound remap
        # (a depth-p ring holds exactly rows < min(p+1, Tc) valid), only
        # the dense-fallback k_pos reconstruction does
        widx = q_pos % Tc
        if scalar and S == 1:
            widx = widx[0, 0]                             # dus fast path
        k_pos = _ring_k_pos(p + n - 1, Tc)[:, :Tb]
    else:
        # scalar uniform pos writes S contiguous rows -> fast dus path
        widx = pos if scalar else q_pos
        k_pos = jnp.arange(Tb)
    if n_valid is not None:
        # padded chunk tokens (i >= n_valid) write to row Tc -> dropped
        widx = jnp.where(offs[None] < n[:, None], widx, Tc)
    return {"q_pos": q_pos, "widx": widx, "k_pos": k_pos,
            "lengths": lengths, "Tb": Tb}


def _decode_ffn(p_l, cfg, x):
    """Dense-or-MoE FFN half of a decode layer (B*S tokens; S=1 decode
    ticks, S=chunk during chunked prefill).

    MoE capacity is pinned to the step's token count (drop-free):
    per-token routing stays independent of which other requests share the
    batch, so continuous batching is token-identical to per-request
    decode and padded chunk tokens cannot evict real ones."""
    h = L.rms_norm(x, p_l["ln_ffn"], cfg.logical_norm_eps)
    if "moe" in p_l:
        f, _ = moe_lib.moe_block(p_l["moe"], cfg.moe, h, mesh=None,
                                 activation=cfg.ffn_activation,
                                 capacity=h.shape[0] * h.shape[1])
    else:
        f = L.ffn_block(p_l["ffn"], h, cfg.ffn_activation)
    return x + f


def decode_attn(p_l, cfg, x, cache, layer, pos, window, cross=None,
                pinfo=None, n_valid=None, kv_len=None, use_ragged=False,
                paged=None):
    """Single-step attention for layer `layer`, reading and updating the
    segment's stacked caches in place.

    cache: {"k", "v"} stacked (n, B, T, Hk, Dh) post-RoPE keys/values
    (+ "k_scale"/"v_scale" (n, B, T, Hk) f32 when kv_cache_dtype is
    quantized); layer: int32 index into the stack. Only the step's new
    rows are written — k_new/v_new are quantized as they land (per-head,
    per-position amax scales), codes and scales share `widx` so ring
    wraparound and padded-token drops stay in lockstep — and reads take
    rows of that layer where they lie: the ragged kernel indexes the
    stack by layer, the dense fallback slices Tb rows of the layer.

    x: (B, S, d) — S is 1 for decode ticks, the chunk size during chunked
    prefill (padded tokens suppressed via n_valid). pos: scalar or
    per-slot (B,). Windowed segments use a ring cache (T == min(max_len,
    window)): writes wrap at pos % T and key positions are reconstructed
    per slot. pinfo: hoisted decode_positions dict (decode_segment builds
    it once per segment); kv_len: static read-slice bucket; use_ragged:
    route S=1 attention through the length-aware Pallas kernel.

    paged: optional (block_table, page, Tc) — caches are then stacked ROW
    POOLS ((n, R, Hk, Dh) / (n, R, Hk) scales, no batch axis) addressed
    through the per-slot block table: `widx` translates to physical rows
    for writes (codes + scales share the translated rows, so page
    lockstep is automatic) and dense reads gather logical rows 0..Tb-1
    in order, making paged attention bitwise-identical to the contiguous
    slice. S=1 ragged decode skips the gather entirely: the Pallas kernel
    indexes KV pages through the block table itself. Returns (x, the
    updated cache dict)."""
    if paged is not None:
        block_table, page, T = paged
        R = cache["k"].shape[1]
    else:
        T = cache["k"].shape[2]
    # windows are static Segment.window ints; a traced window must fail
    # loudly here — silently treating it as full attention would write
    # past a ring-sized cache.
    ring = int(window) > 0
    spec = kv_quant_spec(cfg)
    if pinfo is None:
        pinfo = decode_positions(pos, x.shape[1], T, ring, n_valid=n_valid,
                                 kv_len=kv_len)
    q_pos, widx, k_pos, Tb = (pinfo["q_pos"], pinfo["widx"], pinfo["k_pos"],
                              pinfo["Tb"])
    S = x.shape[1]

    def read(leaf):
        # dense reads: logical rows 0..Tb-1 of this layer, in order
        if paged is not None:
            return leaf[layer, _gather_rows(block_table, page, Tb)]
        return _layer_rows(leaf, layer, Tb)

    def kernel_rows(leaf):
        # the rows of this layer a kernel may read, where they lie
        return leaf[layer] if paged is not None \
            else _layer_rows(leaf, layer, Tb)

    # A multi-token chunk on a RING cache cannot use write-then-read: the
    # chunk's writes at rows (pos+j) % W destroy positions pos+j-W that
    # EARLIER chunk queries still need. The speculative fused-verify path
    # (serve/speculative.py) reads BEFORE writing instead: capture the
    # pre-chunk window here, attend over [pre-chunk rows, fresh chunk
    # keys] below. S=1 decode ticks keep the write-then-read fast path.
    chunk_ring = ring and S > 1
    if chunk_ring:
        pre_k, pre_v = read(cache["k"]), read(cache["v"])
        pre_scales = ((read(cache["k_scale"]), read(cache["v_scale"]))
                      if spec.quantized else None)
    h = L.rms_norm(x, p_l["ln_attn"], cfg.logical_norm_eps)
    # project current token k, v and write to cache
    src = h
    k_new = jnp.einsum("bsd,dhk->bshk", src, p_l["attn"]["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dhk->bshk", src, p_l["attn"]["wv"].astype(x.dtype))
    if cfg.qk_norm:
        k_new = L.rms_norm(k_new, p_l["attn"]["k_norm"])
    if not cfg.use_rel_pos_bias:
        k_new = L.apply_rope(k_new, q_pos, cfg.rope_theta)
    new = {"k": k_new, "v": v_new}
    if spec.quantized:
        # quantize-on-write: post-RoPE keys/values -> codes + scales
        k_new, new["k_scale"] = quant.quantize(k_new, spec)  # (B,S,Hk,dh),
        v_new, new["v_scale"] = quant.quantize(v_new, spec)  # (B,S,Hk)
        new["k"], new["v"] = k_new, v_new
    cache = dict(cache)
    if paged is not None:
        # codes and scales land at the SAME translated physical rows:
        # quantized lockstep holds per page by construction
        rows = _paged_rows(block_table, widx, page, T, R)
        for name, val in new.items():
            cache[name] = cache[name].at[layer, rows].set(
                val.astype(cache[name].dtype), mode="drop")
    else:
        for name, val in new.items():
            cache[name] = _update_at(cache[name], val, layer, widx)
    if chunk_ring:
        # pre-chunk key positions at depth pos (last written pos-1);
        # never-written rows go past every chunk query (pos + S). Fresh
        # chunk keys sit at their own q_pos; padded chunk tokens (widx
        # remapped to T by decode_positions) are pushed out of range too.
        # The quantized path attends the DEQUANTIZED codes of the fresh
        # keys — the same quantize->dequantize round-trip every later
        # read sees, so chunked verify is bit-identical to S=1 decode.
        fresh_k, fresh_v = k_new, v_new
        if spec.quantized:
            pre_k = quant.dequantize(pre_k, pre_scales[0], x.dtype)
            pre_v = quant.dequantize(pre_v, pre_scales[1], x.dtype)
            fresh_k = quant.dequantize(k_new, new["k_scale"], x.dtype)
            fresh_v = quant.dequantize(v_new, new["v_scale"], x.dtype)
        pre_pos = _ring_k_pos(jnp.asarray(pos) - 1, T,
                              far_offset=S + 1)[:, :Tb]
        chunk_pos = jnp.where(widx < T, q_pos, q_pos[:, -1:] + 1)
        kcat = jnp.concatenate(
            [pre_k, jnp.broadcast_to(fresh_k, (pre_k.shape[0],)
                                     + fresh_k.shape[1:])], axis=1)
        vcat = jnp.concatenate(
            [pre_v, jnp.broadcast_to(fresh_v, (pre_v.shape[0],)
                                     + fresh_v.shape[1:])], axis=1)
        kp = jnp.concatenate(
            [jnp.broadcast_to(pre_pos, (chunk_pos.shape[0], Tb)),
             chunk_pos], axis=1)
        a, _ = L.attention_block(p_l["attn"], cfg, h, window=window,
                                 q_pos=q_pos, k_pos=kp, kv=(kcat, vcat))
    elif use_ragged and S == 1:
        # the ragged kernel reads layer `layer` of the stacked codes in
        # place (and, paged, gathers KV pages through the block table in
        # its own index map): no per-layer slice or row gather. Scales
        # go over as this layer's read rows (paged: the layer's pool): a
        # TPU lays a scale stack out position-minor (a row is Hk floats)
        # and XLA relayouts a kernel operand whole, so the operand is the
        # rows read, not the stack.
        lengths = jnp.broadcast_to(pinfo["lengths"], (x.shape[0],))
        kv_scales = ((kernel_rows(cache["k_scale"]),
                      kernel_rows(cache["v_scale"]))
                     if spec.quantized else None)
        a, _ = L.attention_block(
            p_l["attn"], cfg, h, window=window, q_pos=q_pos, k_pos=k_pos,
            kv=(cache["k"], cache["v"]), ragged=(lengths, layer, Tb),
            kv_scales=kv_scales,
            paged_kv=None if paged is None else (block_table, page))
    else:
        # dense fallback: O(bucket) rows, not O(T) — rows past the kv-len
        # bucket are allocated-but-unwritten (masked anyway), never read;
        # paged reads are bitwise-identical to the contiguous slice
        kv_scales = ((read(cache["k_scale"]), read(cache["v_scale"]))
                     if spec.quantized else None)
        a, _ = L.attention_block(p_l["attn"], cfg, h, window=window,
                                 q_pos=q_pos, k_pos=k_pos,
                                 kv=(read(cache["k"]), read(cache["v"])),
                                 kv_scales=kv_scales)
    x = x + a
    if cross is not None:
        cp, ck, cv = cross
        h = L.rms_norm(x, cp["ln_cross"], cfg.logical_norm_eps)
        c, _ = L.attention_block(cp["cross"], cfg, h,
                                 window=jnp.zeros((), jnp.int32),
                                 q_pos=q_pos, k_pos=jnp.arange(ck.shape[1]),
                                 kv=(ck, cv), causal=False)
        x = x + c
    return _decode_ffn(p_l, cfg, x), cache


def decode_mla(p_l, cfg, x, cache, layer, pos, pinfo=None, n_valid=None,
               kv_len=None, paged=None):
    """MLA for layer `layer` of the segment's stacked latent caches,
    updated in place. cache: {"latent": (n, B, T, r+qr)} (+
    "latent_scale" (n, B, T) quantized). pos: scalar or per-slot (B,).
    MLA caches are always linear (full attention); the latent read is
    bucket-sliced like the k/v caches. Quantized mode stores latent
    codes + a per-position scale (the latent is head-free, so one scale
    per cached row); the absorbed-matrix attention consumes the densely-
    dequantized slice (no MLA Pallas kernel — the dequant IS the
    reference path). paged: optional (block_table, page, Tc) — latents
    pool their rows exactly like the k/v caches (decode_attn), codes +
    scales on the same physical rows. Returns (out, cache dict)."""
    if paged is not None:
        block_table, page, T = paged
        R = cache["latent"].shape[1]
    else:
        T = cache["latent"].shape[2]
    spec = kv_quant_spec(cfg)
    if pinfo is None:
        pinfo = decode_positions(pos, x.shape[1], T, False, n_valid=n_valid,
                                 kv_len=kv_len)
    q_pos, widx, Tb = pinfo["q_pos"], pinfo["widx"], pinfo["Tb"]
    h = L.rms_norm(x, p_l["ln_attn"], cfg.logical_norm_eps)
    new = {"latent": L.mla_latent(p_l["attn"], cfg, h, k_pos=q_pos)}
    if spec.quantized:                                  # scale (B,S)
        new["latent"], new["latent_scale"] = quant.quantize(new["latent"],
                                                            spec)
    cache = dict(cache)
    if paged is not None:
        rows = _paged_rows(block_table, widx, page, T, R)
        grows = _gather_rows(block_table, page, Tb)
        for name, val in new.items():
            cache[name] = cache[name].at[layer, rows].set(
                val.astype(cache[name].dtype), mode="drop")
        read = lambda leaf: leaf[layer, grows]
    else:
        for name, val in new.items():
            cache[name] = _update_at(cache[name], val, layer, widx)
        read = lambda leaf: _layer_rows(leaf, layer, Tb)
    latr = read(cache["latent"])
    if spec.quantized:
        latr = quant.dequantize(latr, read(cache["latent_scale"]), x.dtype)
    a = L.mla_attention(p_l["attn"], cfg, h, latr, q_pos=q_pos,
                        k_pos=pinfo["k_pos"])
    x = x + a
    return _decode_ffn(p_l, cfg, x), cache


# Segment kinds whose caches are indexed by position (KV caches): their
# stacks ride in the decode loop's carry and each layer writes only its
# new rows. Recurrent kinds (rwkv/mamba) rewrite their whole state every
# step, so their per-layer state stays scan xs/ys.
_KV_KINDS = ("attn", "mla")


def decode_segment(p_seg, cache, seg: Segment, cfg: ModelConfig, x, pos,
                   *, mesh=None, cross_stack=None, n_valid=None,
                   kv_len=None, use_ragged=False, use_fused=False,
                   paged=None):
    """x: (B, S, [K,] d); returns (x, new cache). S > 1 only during
    chunked prefill (attention/MLA segments; padded tokens masked via
    n_valid). paged: optional (block_table, page) — row-pooled caches
    addressed through the per-slot table; the logical capacity Tc is the
    table's row span (ring segments still cap it at their window), which
    covers every reachable position since requests never exceed max_len
    <= table capacity.

    KV-cache segments (_KV_KINDS) carry their stacked caches through the
    layer scan with the layer index: each layer writes its new rows into
    the stack and reads its layer where it lies, so no per-layer slice of
    a cache and no rebuilt copy of the stack is ever materialized. A
    segment of seg.n layers reads and writes layers 0..seg.n-1 of a stack
    that may hold more (draft_step's head layers)."""
    K = cfg.altup.K
    S = x.shape[1]
    pg_seg = None
    # hoisted position construction (§Perf satellite): q_pos / k_pos /
    # write rows / ragged lengths are identical for every layer of the
    # segment, so build them once HERE — outside the scanned layer body —
    # instead of re-deriving the (S, T) position grids per layer per step.
    if seg.kind in ("attn", "shared_attn"):
        if paged is not None:
            bt, pg = paged
            T_pg = bt.shape[1] * pg
            Tc = min(T_pg, int(seg.window)) if int(seg.window) > 0 \
                else T_pg
            pg_seg = (bt, pg, Tc)
        else:
            Tc = (cache["k"].shape[1] if seg.kind == "shared_attn"
                  else cache["k"].shape[2])
        pinfo = decode_positions(pos, S, Tc, int(seg.window) > 0,
                                 n_valid=n_valid, kv_len=kv_len)
    elif seg.kind == "mla":
        if paged is not None:
            bt, pg = paged
            Tc = bt.shape[1] * pg
            pg_seg = (bt, pg, Tc)
        else:
            Tc = cache["latent"].shape[2]
        pinfo = decode_positions(pos, S, Tc, False, n_valid=n_valid,
                                 kv_len=kv_len)
    else:
        pinfo = None

    if seg.kind == "shared_attn":
        # one unstacked block: its caches are a stack of one (a free
        # leading axis) read and written at layer 0
        def layer_fn(xa):
            out, nc = decode_attn(p_seg, cfg, xa,
                                  {k: c[None] for k, c in cache.items()},
                                  0, pos, seg.window, pinfo=pinfo,
                                  use_ragged=use_ragged, paged=pg_seg)
            layer_fn.new_cache = {k: c[0] for k, c in nc.items()}
            return out
        if cfg.altup.enabled:
            sel = alt.block_selector(seg.layer_offset, K, cfg.altup.selection)
            x = alt.altup_layer(layer_fn, x, sel, p_seg["altup_p"],
                                p_seg["altup_g"], use_fused=use_fused)
        else:
            x = layer_fn(x)
        return x, layer_fn.new_cache

    n = seg.n
    kv = seg.kind in _KV_KINDS
    sels = (jnp.stack([alt.block_selector(i, K, cfg.altup.selection)
                       for i in range(seg.layer_offset,
                                      seg.layer_offset + n)])
            if cfg.altup.enabled else jnp.zeros((n, 1)))

    def body(carry, per_layer):
        x, stack = carry
        p_l, state, sel, cross_l, layer = per_layer
        box = {}

        def layer_fn(xa):
            if seg.kind == "attn":
                cross = None
                if cross_l is not None:
                    cross = (cross_l[0], cross_l[1]["k"], cross_l[1]["v"])
                out, box["stack"] = decode_attn(
                    p_l, cfg, xa, stack, layer, pos, seg.window,
                    cross=cross, pinfo=pinfo, use_ragged=use_ragged,
                    paged=pg_seg)
            elif seg.kind == "mla":
                out, box["stack"] = decode_mla(p_l, cfg, xa, stack, layer,
                                               pos, pinfo=pinfo,
                                               paged=pg_seg)
            elif seg.kind == "rwkv":
                from repro.models.transformer import rwkv_layer
                out, _, st = rwkv_layer(p_l, cfg, xa, dict(state))
                box["state"] = {"wkv": st["wkv"],
                                "shift_tm": st["shift_tm"],
                                "shift_cm": st["shift_cm"]}
            elif seg.kind == "mamba":
                from repro.models.transformer import mamba_layer
                out, _, st = mamba_layer(p_l, cfg, xa, dict(state))
                box["state"] = {"conv": st["conv"], "ssm": st["ssm"]}
            else:
                raise ValueError(seg.kind)
            return out

        if cfg.altup.enabled:
            x = alt.altup_layer(layer_fn, x, sel, p_l["altup_p"],
                                p_l["altup_g"], use_fused=use_fused)
        else:
            x = layer_fn(x)
        return (x, box.get("stack")), box.get("state")

    xs = (p_seg, None if kv else cache, sels, cross_stack,
          jnp.arange(n, dtype=jnp.int32))
    (x, stack), states = jax.lax.scan(
        body, (x, cache if kv else None), xs,
        unroll=seg.n if cfg.scan_unroll else 1)
    return x, (stack if kv else states)


def decode_step(params, cfg: ModelConfig, caches, tokens, pos, *,
                n_valid=None, kv_len=None, mesh=None, block_table=None,
                page_size=0):
    """serve_step: advance every sequence by its next token(s).

    tokens: (B, S) int32 — S is 1 for decode ticks; chunked prefill feeds
    S = chunk tokens per slot (padded slots masked by n_valid). pos:
    int32 position — scalar (uniform static batch) or (B,) per-slot
    vector (continuous batching: each sequence sits at its own depth).
    n_valid: optional (B,) count of real tokens per slot this step —
    padded tokens neither write the cache nor produce usable logits.
    kv_len: optional STATIC read-slice bucket (host-computed power-of-two
    >= max fill depth): attention reads O(kv_len) cache rows, not O(T).
    block_table/page_size: PAGED mode — caches are init_paged_cache row
    pools and block_table is the (B, ceil(max_len/page)) int32 per-slot
    page map (page_size is static). Returns (logits (B, S, V), new
    caches); sampling reads row n_valid-1 per slot.
    """
    from repro.kernels import resolve_kernel_flag
    paged = None
    if block_table is not None:
        assert int(page_size) >= 1, "paged decode needs a page_size"
        assert cfg.family != "encdec", "paged decode is decoder-only"
        assert jnp.asarray(pos).ndim == 1, \
            "paged decode needs per-slot (B,) positions"
        paged = (block_table, int(page_size))
    use_ragged = resolve_kernel_flag(cfg.ragged_decode_attn)
    use_fused = cfg.altup.enabled and \
        resolve_kernel_flag(cfg.fused_decode_altup)
    x = embed_tokens(params, cfg, tokens)
    x = _shard(x, mesh, P(batch_axes(mesh), *([None] * (x.ndim - 1))))
    new_caches = dict(caches)
    segs = layer_plan(cfg)
    for si, seg in enumerate(segs):
        cross_stack = None
        if cfg.family == "encdec" and seg.kind == "attn":
            cross_stack = (params["enc"]["cross"], caches["cross"])
        p_seg = (params["shared_blk"] if seg.kind == "shared_attn"
                 else params[f"seg{si}"])
        x, nc = decode_segment(p_seg, caches[f"seg{si}"], seg,
                               cfg, x, pos, mesh=mesh,
                               cross_stack=cross_stack, n_valid=n_valid,
                               kv_len=kv_len, use_ragged=use_ragged,
                               use_fused=use_fused, paged=paged)
        new_caches[f"seg{si}"] = nc
    logits = unembed(params, cfg, x, mesh=mesh)
    return logits, new_caches


def decode_sample_step(params, caches, seen, tokens, pos, n_valid, sparams,
                       *, cfg: ModelConfig, kv_len=None, want_logprobs=False,
                       any_sampled=True, mesh=None, block_table=None,
                       page_size=0):
    """Fused decode + ON-DEVICE sampling — the serving hot path's step.

    Runs decode_step, gathers each slot's sampled logits row (row
    n_valid-1, vocab-truncated) on device, folds this step's fed tokens
    into the repetition-penalty `seen` table, and samples per slot under
    the per-request keys in `sparams` (serve/sampling.sample_rows). Only
    the (B,) sampled ids — plus the (B,) chosen-token logprobs when
    want_logprobs — ever leave the device; the (B, V) rows never
    transfer to host (the v2 API's hot-path contract; the pre-v2 engine
    shipped a full (B, V) f32 row per step and sampled in numpy).

    seen: (B, V) bool per-slot consumed-token table (engine clears a
    slot's row at admission). sparams: per-slot parameter arrays from
    serve/sampling.blank_slot_params. Returns (ids, logprobs|None,
    new caches, new seen)."""
    from repro.serve.sampling import sample_rows, update_seen
    logits, caches = decode_step(params, cfg, caches, tokens, pos,
                                 n_valid=n_valid, kv_len=kv_len, mesh=mesh,
                                 block_table=block_table,
                                 page_size=page_size)
    B = tokens.shape[0]
    rows = logits[jnp.arange(B), jnp.maximum(n_valid - 1, 0),
                  :cfg.vocab_size]
    seen = update_seen(seen, tokens, n_valid)
    # the sampler's ops carry the `sample` scope in their op names, so a
    # profile can sum its device time inside the fused program
    with jax.named_scope("sample"):
        ids, lps = sample_rows(rows, sparams, seen,
                               want_logprobs=want_logprobs,
                               any_sampled=any_sampled)
    return ids, lps, caches, seen


def _tree_head(tree, m: int):
    """First m stacked layers of a segment's param/cache pytree."""
    return jax.tree_util.tree_map(lambda l: l[:m], tree)


def _tree_merge(old, new, m: int):
    """Merge updated head layers back over the untouched tail."""
    return jax.tree_util.tree_map(
        lambda o, n: jnp.concatenate([n, o[m:]], axis=0), old, new)


def draft_step(params, cfg: ModelConfig, caches, tokens, pos, *,
               draft_layers: int, n_valid=None, kv_len=None, mesh=None,
               block_table=None, page_size=0):
    """Predict-only / early-exit DRAFT forward for self-speculative
    decoding (serve/speculative.py).

    Runs the first `draft_layers` layers exactly as decode_step would —
    including their cache writes, so the draft's K/V land in the slot
    caches at their true positions (the draft-KV scratch IS the main
    cache: the verify chunk rewrites those rows with bit-identical
    values, since layers below the exit compute the same activations) —
    then collapses every remaining layer to its AltUp PREDICT step. The
    skipped tail of each segment is one composed K x K mixer
    (core/altup.compose_predictors): predict is linear in the widened
    stream, so L-D skipped layers cost K^2 scalars per token, zero
    attention/FFN compute and zero cache traffic. With AltUp disabled
    the tail is the identity (a plain early exit).

    Same signature/contract as decode_step minus encdec support; returns
    (logits (B, S, V), new caches).
    """
    import dataclasses
    from repro.kernels import resolve_kernel_flag
    assert cfg.family != "encdec", "draft_step serves decoder-only models"
    D = int(draft_layers)
    assert 1 <= D <= cfg.n_layers, f"draft_layers={D} out of range"
    paged = (block_table, int(page_size)) if block_table is not None \
        else None
    use_ragged = resolve_kernel_flag(cfg.ragged_decode_attn)
    use_fused = cfg.altup.enabled and \
        resolve_kernel_flag(cfg.fused_decode_altup)
    K = cfg.altup.K
    x = embed_tokens(params, cfg, tokens)
    x = _shard(x, mesh, P(batch_axes(mesh), *([None] * (x.ndim - 1))))
    new_caches = dict(caches)
    for si, seg in enumerate(layer_plan(cfg)):
        p_seg = (params["shared_blk"] if seg.kind == "shared_attn"
                 else params[f"seg{si}"])
        cache = caches[f"seg{si}"]
        m = min(max(D - seg.layer_offset, 0), seg.n)   # full-compute layers
        if m == seg.n:
            x, nc = decode_segment(p_seg, cache, seg, cfg, x, pos,
                                   mesh=mesh, n_valid=n_valid,
                                   kv_len=kv_len, use_ragged=use_ragged,
                                   use_fused=use_fused, paged=paged)
            new_caches[f"seg{si}"] = nc
            continue
        if m > 0:
            # partial segment: run the head layers through the normal
            # scanned body on sliced param stacks. KV stacks are written
            # in place at layers 0..m-1; recurrent state is sliced to the
            # head and merged back over the untouched tail layers
            head = dataclasses.replace(seg, n=m)
            kv = seg.kind in _KV_KINDS
            x, nc = decode_segment(_tree_head(p_seg, m),
                                   cache if kv else _tree_head(cache, m),
                                   head, cfg, x, pos, mesh=mesh,
                                   n_valid=n_valid, kv_len=kv_len,
                                   use_ragged=use_ragged,
                                   use_fused=use_fused, paged=paged)
            new_caches[f"seg{si}"] = nc if kv else _tree_merge(cache, nc, m)
        if cfg.altup.enabled:
            # predict-only tail: layers [m, n) collapse to ONE composed
            # K x K mixer (shared_attn blocks carry an unstacked (K, K))
            if seg.kind == "shared_attn":
                comp = p_seg["altup_p"]
            else:
                comp = alt.compose_predictors(p_seg["altup_p"], start=m)
            x = alt.predict(x, comp)
    logits = unembed(params, cfg, x, mesh=mesh)
    return logits, new_caches


# Recurrent cache leaves carry history that attention masking cannot
# neutralize — they must be zeroed when a slot is recycled. Attention
# k/v/latent leaves self-clean: a recycled slot rewrites positions
# 0..pos sequentially and the causal mask hides everything beyond.
_RECURRENT_LEAVES = ("wkv", "shift_tm", "shift_cm", "ssm", "conv")
# Quantized-cache scale leaves are cleared too: rows < the new request's
# fill depth are rewritten anyway, but zeroing the rest makes every
# stale row dequantize to exact 0 (scale 0), so a recycled slot can
# never leak another request's magnitudes through a bad lengths bug and
# a NaN/Inf scale from an aborted request cannot survive recycling.
_SCALE_LEAVES = ("k_scale", "v_scale", "latent_scale")


def copy_prefix(caches, dst, src, p, *, copy_recurrent=False):
    """Clone the first `p` cache positions of slot `src` into slot `dst`
    across every cache leaf — the jitted slot-to-slot copy behind the
    engine's prefix-cache hits (serve/scheduler.PrefixIndex).

    dst/src/p are traced scalars, so ONE compilation covers every hit at
    every prefix length. Row-indexed leaves — attention k/v, MLA latents,
    and their quantized scale siblings (codes and scales copy in
    LOCKSTEP, so an int8/fp8 prefix reuses without a dequant round-trip)
    — copy rows < min(p, Tc): for a full-length cache that is rows
    0..p-1; for a W-slot ring cache the copy collapses to the last
    min(p, W) prefix positions, whose ring indices q % W are exactly
    rows 0..min(p,W)-1 under the engine's donor-validity rule (donor
    depth <= max(p, W): the donor never wrapped past the prefix, so the
    wraparound linearization is the identity and no remap is needed).
    Recurrent leaves (rwkv/mamba state) have no position axis;
    copy_recurrent=True clones the whole slot state, which is exact only
    when the donor stopped at the prefix boundary (depth == p — the
    engine's recurrent validity gate). src == dst is a no-op (the
    self-donor admission path reuses an evicted donor's rows in place).
    """
    dst = jnp.asarray(dst, jnp.int32)
    src = jnp.asarray(src, jnp.int32)
    p = jnp.asarray(p, jnp.int32)

    def rows_copy(leaf, b_ax, t_ax):
        Tc = leaf.shape[t_ax]
        keep = jnp.arange(Tc) < jnp.minimum(p, Tc)
        shape = [1] * (leaf.ndim - 1)          # b_ax < t_ax for all leaves
        shape[t_ax - 1] = Tc
        src_rows = jnp.take(leaf, src, axis=b_ax)
        dst_rows = jnp.take(leaf, dst, axis=b_ax)
        merged = jnp.where(keep.reshape(shape), src_rows, dst_rows)
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.expand_dims(merged, b_ax), dst, axis=b_ax)

    def copy(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v"):                 # stacked 5-D | shared 4-D
            return rows_copy(leaf, *((1, 2) if leaf.ndim == 5 else (0, 1)))
        if name in ("k_scale", "v_scale"):     # stacked 4-D | shared 3-D
            return rows_copy(leaf, *((1, 2) if leaf.ndim == 4 else (0, 1)))
        if name in ("latent", "latent_scale"):  # always stacked (n,B,T,.)
            return rows_copy(leaf, 1, 2)
        if name in _RECURRENT_LEAVES:          # stacked (n, B, ...)
            if not copy_recurrent:
                return leaf
            state = jnp.take(leaf, src, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, jnp.expand_dims(state, 1), dst, axis=1)
        return leaf

    return jax.tree_util.tree_map_with_path(copy, caches)


def reset_slot(caches, slot, *, only_recurrent=False):
    """Zero one slot's recurrent state (rwkv/mamba) and any quantized-
    cache scale leaves across all segments.

    slot: scalar int32 (traced OK — jit this with donated caches). Attn
    and MLA code/float caches are left untouched; per-slot position
    masking makes their stale rows unreachable. only_recurrent=True
    (PAGED caches) skips the scale leaves: paged scale leaves are row
    pools with no batch axis — freshly-allocated pages are zeroed by
    reset_pages instead."""

    def reset(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if only_recurrent and name in _SCALE_LEAVES:
            return leaf
        if name in _RECURRENT_LEAVES:
            # all recurrent leaves are stacked (n, B, ...): batch axis 1
            return leaf.at[:, slot].set(jnp.zeros_like(leaf[:, slot]))
        if name in _SCALE_LEAVES:
            # stacked (n, B, T, hk) / (n, B, T) — except the shared-attn
            # block's k/v scales, which are unstacked (B, T, hk): the
            # stacked k/v scales are 4-D and latent_scale is always
            # stacked, so ndim + name disambiguates the batch axis
            stacked = name == "latent_scale" or leaf.ndim == 4
            if stacked:
                return leaf.at[:, slot].set(jnp.zeros_like(leaf[:, slot]))
            return leaf.at[slot].set(jnp.zeros_like(leaf[slot]))
        return leaf

    return jax.tree_util.tree_map_with_path(reset, caches)


# --------------------------------------------------------------------------
# page-granular cache ops (serve/paging.py drives these, jitted, on the
# init_paged_cache row pools)
# --------------------------------------------------------------------------
# All four take fixed-width (K,) int32 page-id vectors padded with -1 so
# ONE compilation covers every page count up to K: padded destination
# pages remap to the out-of-range row block [R, R+page) and the scatter
# drops them; padded source pages clamp to page 0 and gather unused
# garbage. Only row-pooled leaves participate (_paged_row_axis);
# recurrent per-slot state keeps the slot-granular copy/reset helpers.


def _page_rows(pages, page: int, *, pad_to):
    """(K,) page ids -> (K * page,) row ids; padded (< 0) entries map to
    the page starting at row `pad_to` (pass R to drop, 0 to clamp)."""
    pages = jnp.asarray(pages, jnp.int32)
    base = jnp.where(pages >= 0, pages * page, pad_to)
    offs = jnp.arange(page, dtype=jnp.int32)[None]
    return (base[:, None] + offs).reshape(-1)


def copy_pages(caches, dst_pages, src_pages, *, page: int):
    """Clone whole physical pages src -> dst across every row-pooled
    leaf — codes AND scales in lockstep, ring/latent pools included.
    The jitted page-copy behind partial-boundary-page prefix hits and
    ring-plan prefix clones (aliased full pages never copy)."""

    def copy(path, leaf):
        ax = _paged_row_axis(_leaf_name(path), leaf.ndim)
        if ax is None:
            return leaf
        R = leaf.shape[ax]
        src_rows = _page_rows(src_pages, page, pad_to=0)
        dst_rows = _page_rows(dst_pages, page, pad_to=R)
        vals = jnp.take(leaf, src_rows, axis=ax)
        if ax == 1:
            return leaf.at[:, dst_rows].set(vals, mode="drop")
        return leaf.at[dst_rows].set(vals, mode="drop")

    return jax.tree_util.tree_map_with_path(copy, caches)


def gather_pages(caches, pages, *, page: int):
    """Gather the given pages of every row-pooled leaf into a compact
    blob pytree (page i of the blob == pages[i]; padded entries gather
    page 0, ignored on restore). The device half of a host-tier spill —
    the engine np.asarray()s the result before releasing the pages."""

    def gather(path, leaf):
        ax = _paged_row_axis(_leaf_name(path), leaf.ndim)
        if ax is None:
            return jnp.zeros((0,), leaf.dtype)        # not spilled
        rows = _page_rows(pages, page, pad_to=0)
        return jnp.take(leaf, rows, axis=ax)

    return jax.tree_util.tree_map_with_path(gather, caches)


def scatter_pages(caches, blob, pages, *, page: int):
    """Scatter a gather_pages blob back into the given pages (padded
    entries dropped) — the restore half of the host spill tier."""

    def scatter(path, leaf_and_blob):
        leaf, bl = leaf_and_blob
        ax = _paged_row_axis(_leaf_name(path), leaf.ndim)
        if ax is None:
            return leaf
        R = leaf.shape[ax]
        rows = _page_rows(pages, page, pad_to=R)
        if ax == 1:
            return leaf.at[:, rows].set(bl.astype(leaf.dtype), mode="drop")
        return leaf.at[rows].set(bl.astype(leaf.dtype), mode="drop")

    merged = jax.tree_util.tree_map(lambda a, b: (a, b), caches, blob)
    return jax.tree_util.tree_map_with_path(
        scatter, merged, is_leaf=lambda x: isinstance(x, tuple))


def reset_pages(caches, pages, *, page: int):
    """Zero the quantized scale rows of freshly-allocated pages. The
    paged counterpart of reset_slot's scale sweep: a recycled page's
    stale VALUE rows may hold NaN/Inf from an aborted request, and the
    dense fallback multiplies values by scales before masking — scale 0
    makes every stale row dequantize to exact 0 so nothing can poison
    the softmax through 0 * NaN. Aliased (shared) pages are never
    reset — they carry the donor's live scales."""

    def reset(path, leaf):
        name = _leaf_name(path)
        if name not in _SCALE_LEAVES:
            return leaf
        ax = _paged_row_axis(name, leaf.ndim)
        R = leaf.shape[ax]
        rows = _page_rows(pages, page, pad_to=R)
        if ax == 1:
            return leaf.at[:, rows].set(0.0, mode="drop")
        return leaf.at[rows].set(0.0, mode="drop")

    return jax.tree_util.tree_map_with_path(reset, caches)


# --------------------------------------------------------------------------
# speculative-decoding cache rollback (serve/speculative.py)
# --------------------------------------------------------------------------
# Linear (full-attention) k/v and MLA latent caches need NO restore on a
# rejected speculative suffix: rows past the committed position are masked
# by per-slot positions and rewritten before they become visible, and the
# quantized scale leaves share the write index so they stay in lockstep.
# RING caches are the exception — a chunk write at row (pos+j) % W
# DESTROYS position pos+j-W, which surviving queries still need after a
# rewind — so the engine snapshots the S rows a speculative round will
# touch before drafting and restores the rejected suffix afterwards.


def _ring_segs(cfg: ModelConfig):
    """(seg_name, stacked?, window) for every ring-cache segment."""
    out = []
    for si, seg in enumerate(layer_plan(cfg)):
        if seg.kind in ("attn", "shared_attn") and seg.window > 0:
            out.append((f"seg{si}", seg.kind == "attn", int(seg.window)))
    return out


def _ring_rows(leaf, stacked: bool, pos, S: int):
    """(B,) pos -> the (B, S) ring rows positions pos..pos+S-1 occupy."""
    Tc = leaf.shape[2 if stacked else 1]
    B = leaf.shape[1 if stacked else 0]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    return (p[:, None] + jnp.arange(S, dtype=jnp.int32)[None]) % Tc


def _ring_rows_paged(block_table, page: int, window: int, pos, S: int):
    """Paged form of _ring_rows: the (B, S) PHYSICAL pool rows that ring
    positions pos..pos+S-1 occupy, through the block table. The logical
    ring capacity matches decode_segment: min(table span, window)."""
    Tc = min(block_table.shape[1] * page, window)
    B = block_table.shape[0]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    logical = (p[:, None] + jnp.arange(S, dtype=jnp.int32)[None]) % Tc
    phys = jnp.take_along_axis(block_table, logical // page, axis=1)
    return phys * page + logical % page


def snapshot_rows(cfg: ModelConfig, caches, pos, S: int, *,
                  block_table=None, page: int = 0):
    """Capture the ring-cache rows (codes AND quantized scales, in
    lockstep) that speculative positions pos..pos+S-1 will overwrite.
    Returns {seg_name: {leaf: (n, B, S, ...) | (B, S, ...)}} — empty for
    plans with no ring segment. S must not exceed the smallest ring
    window (the engine caps the draft length so one round never wraps a
    row onto itself). block_table/page: PAGED pools — rows translate
    through the table, snapshot shapes are identical to contiguous."""
    snap = {}
    for name, stacked, window in _ring_segs(cfg):
        c = caches[name]
        entry = {}
        for leaf_name in ("k", "v", "k_scale", "v_scale"):
            if leaf_name not in c:
                continue
            leaf = c[leaf_name]
            if block_table is not None:
                rows = _ring_rows_paged(block_table, page, window, pos, S)
                entry[leaf_name] = (leaf[:, rows] if stacked
                                    else leaf[rows])
            else:
                rows = _ring_rows(leaf, stacked, pos, S)
                bidx = jnp.arange(rows.shape[0])[:, None]
                entry[leaf_name] = (leaf[:, bidx, rows] if stacked
                                    else leaf[bidx, rows])
        snap[name] = entry
    return snap


def restore_rows(cfg: ModelConfig, caches, snap, pos, start, S: int, *,
                 block_table=None, page: int = 0):
    """Scatter snapshot rows back: slot b restores rows start_b..S-1
    (start is scalar or (B,)). start=0 undoes a whole round's ring
    writes (pre-verify: the draft's ring writes must not shadow the
    window the verify chunk reads); start=n_committed_b is the
    post-verify rollback that rewinds exactly the rejected suffix.
    start >= S restores nothing for that slot."""
    new_caches = dict(caches)
    offs = jnp.arange(S, dtype=jnp.int32)[None]
    for name, stacked, window in _ring_segs(cfg):
        c = dict(caches[name])
        for leaf_name, snap_leaf in snap[name].items():
            leaf = c[leaf_name]
            if block_table is not None:
                ax = _paged_row_axis(leaf_name, leaf.ndim)
                R = leaf.shape[ax]
                rows = _ring_rows_paged(block_table, page, window, pos, S)
                st = jnp.broadcast_to(jnp.asarray(start, jnp.int32),
                                      (rows.shape[0],))
                rows = jnp.where(offs >= st[:, None], rows, R)
                if stacked:
                    c[leaf_name] = leaf.at[:, rows].set(
                        snap_leaf, mode="drop")
                else:
                    c[leaf_name] = leaf.at[rows].set(
                        snap_leaf, mode="drop")
                continue
            Tc = leaf.shape[2 if stacked else 1]
            rows = _ring_rows(leaf, stacked, pos, S)
            B = rows.shape[0]
            st = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
            # rows before each slot's start are remapped out of range ->
            # dropped by the scatter (same trick as padded-token writes)
            rows = jnp.where(offs >= st[:, None], rows, Tc)
            bidx = jnp.arange(B)[:, None]
            if stacked:
                c[leaf_name] = leaf.at[:, bidx, rows].set(
                    snap_leaf, mode="drop")
            else:
                c[leaf_name] = leaf.at[bidx, rows].set(
                    snap_leaf, mode="drop")
        new_caches[name] = c
    return new_caches


def recurrent_checkpoint(caches):
    """Snapshot every recurrent state leaf (rwkv/mamba) — the draft-
    boundary checkpoint. Recurrent state advances token-by-token and
    cannot be rewound mid-chunk, so the speculative engine mode falls
    back to normal decode for recurrent plans (mirroring the chunk=1
    prefill gate); these helpers are the boundary-checkpoint primitive
    for a future per-token recurrent verify."""
    snap = {}
    for seg_name, c in caches.items():
        if not isinstance(c, dict):
            continue
        entry = {k: v for k, v in c.items() if k in _RECURRENT_LEAVES}
        if entry:
            snap[seg_name] = entry
    return snap


def restore_recurrent(caches, snap):
    """Roll every recurrent state leaf back to its checkpoint."""
    new_caches = dict(caches)
    for seg_name, entry in snap.items():
        c = dict(caches[seg_name])
        c.update(entry)
        new_caches[seg_name] = c
    return new_caches


def prefill(params, cfg: ModelConfig, tokens, T: int, *, mesh=None,
            encoder_frames=None, step_fn=None):
    """Run the full prompt and build caches of capacity T (for examples
    and correctness tests — decode_step consumes the result).

    step_fn: optional (params, caches, tokens, pos) -> (logits, caches)
    replacement for the eager decode_step — the serving engine passes its
    jitted step so prefill shares the compiled hot loop. pos reaches
    step_fn as a plain int so the engine can derive its static kv-len
    bucket from it."""
    B, S = tokens.shape
    caches = init_cache(cfg, B, T)
    if cfg.family == "encdec":
        from repro.models.transformer import encode
        enc_out = encode(params, cfg, encoder_frames, mesh=mesh)
        # fill cross caches per decoder layer
        def fill(cross_l):
            k = jnp.einsum("bsd,dhk->bshk", enc_out,
                           cross_l["cross"]["wk"].astype(enc_out.dtype))
            v = jnp.einsum("bsd,dhk->bshk", enc_out,
                           cross_l["cross"]["wv"].astype(enc_out.dtype))
            if not cfg.use_rel_pos_bias:
                k = L.apply_rope(k, jnp.arange(k.shape[1]), cfg.rope_theta)
            return k, v
        ks, vs = jax.vmap(fill)(params["enc"]["cross"])
        caches["cross"] = {"k": ks, "v": vs}
    if step_fn is None:
        step_fn = lambda p, c, tk, ps: decode_step(p, cfg, c, tk, ps,
                                                   mesh=mesh)
    logits = None
    for t in range(S):
        logits, caches = step_fn(params, caches, tokens[:, t: t + 1], t)
    return logits, caches
