"""Core transformer layer primitives: norms, RoPE, GQA/windowed attention,
MLA (DeepSeek latent attention), gated FFNs, T5 relative position bias.

All functions are pure; parameters are plain dicts of jnp arrays. Shapes use
B=batch, S=query length, T=key length, H=heads, Hk=kv heads, Dh=head dim,
D=d_model, F=d_ff.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MLAConfig

NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free
                 # for rows where every position is masked (padding).


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis=-2):
    """Truncated-normal fan-in init (T5 / mup-friendly)."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def embed_init(key, shape, dtype):
    # 1/sqrt(d) keeps tied-logit scale O(1) at init
    std = 1.0 / math.sqrt(shape[-1])
    return (jax.random.normal(key, shape) * std).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def init_rms_norm(d: int, dtype) -> jax.Array:
    # stored as (scale - 1) so zeros == identity (gemma/t5 convention)
    return jnp.zeros((d,), dtype)


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh), positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                          # (Dh/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, Dh/2)
    if angles.ndim == 2:                                   # (S, Dh/2) -> batch
        angles = angles[None]
    cos = jnp.cos(angles)[..., :, None, :]                 # (B, S, 1, Dh/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# T5 relative position bias
# --------------------------------------------------------------------------

def t5_rel_bucket(rel: jax.Array, n_buckets: int, max_dist: int = 128,
                  bidirectional: bool = False) -> jax.Array:
    ret = jnp.zeros_like(rel)
    n = n_buckets
    if bidirectional:
        n = n // 2
        ret = ret + (rel > 0).astype(jnp.int32) * n
        rel = jnp.abs(rel)
    else:
        rel = -jnp.minimum(rel, 0)
    max_exact = n // 2
    is_small = rel < max_exact
    large = max_exact + (
        jnp.log(rel.astype(jnp.float32) / max_exact + 1e-6)
        / math.log(max_dist / max_exact) * (n - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, n - 1)
    return ret + jnp.where(is_small, rel, large)


def t5_rel_bias(rel_table: jax.Array, q_pos: jax.Array, k_pos: jax.Array,
                n_buckets: int, bidirectional: bool) -> jax.Array:
    """rel_table: (n_buckets, H) -> bias (1, H, S, T)."""
    rel = k_pos[None, :] - q_pos[:, None]                  # (S, T)
    buckets = t5_rel_bucket(rel, n_buckets, bidirectional=bidirectional)
    bias = rel_table[buckets]                              # (S, T, H)
    return bias.transpose(2, 0, 1)[None]                   # (1, H, S, T)


# --------------------------------------------------------------------------
# attention (GQA + optional sliding window + optional bias)
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, h, hk = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, dh), dtype, in_axis=0),
        "wk": dense_init(ks[1], (d, hk, dh), dtype, in_axis=0),
        "wv": dense_init(ks[2], (d, hk, dh), dtype, in_axis=0),
        "wo": dense_init(ks[3], (h, dh, d), dtype, in_axis=0),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(dh, dtype)
        p["k_norm"] = init_rms_norm(dh, dtype)
    return p


def sdpa(q, k, v, *, causal: bool, window, q_pos, k_pos, bias=None,
         scale: Optional[float] = None):
    """Scaled dot-product attention with GQA + sliding window masking.

    q: (B, S, H, Dh); k, v: (B, T, Hk, Dh); window: 0/None = full, else
    only attend to keys with q_pos - k_pos < window (traced scalar OK).
    q_pos: (S,) or (B, S); k_pos: (T,) or (B, T).
    """
    B, S, H, Dh = q.shape
    T, Hk = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    rep = H // Hk
    qg = q.reshape(B, S, Hk, rep, Dh)
    scores = jnp.einsum("bshrd,bthd->bhrst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale     # (B,Hk,rep,S,T)
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = k_pos[None]
    rel = q_pos[:, :, None] - k_pos[:, None, :]            # (B, S, T)
    m = jnp.ones(rel.shape, dtype=bool)
    if causal:
        m = m & (rel >= 0)
    if window is not None:
        w = jnp.asarray(window)
        m = m & jnp.where(w > 0, rel < w, True)
    scores = jnp.where(m[:, None, None, :, :], scores, NEG_INF)
    if bias is not None:                                   # (1|B, H, S, T)
        bias = bias.reshape(bias.shape[0], Hk, rep, S, T)
        scores = scores + bias.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrst,bthd->bshrd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, Dh).astype(q.dtype)


def attention_block(p: dict, cfg: ModelConfig, x: jax.Array, *,
                    window, q_pos, k_pos, kv: Optional[tuple] = None,
                    x_kv: Optional[jax.Array] = None, bias=None,
                    causal: Optional[bool] = None, banded: bool = False,
                    ragged: Optional[tuple] = None,
                    kv_scales: Optional[tuple] = None,
                    paged_kv: Optional[tuple] = None):
    """Full attention sub-block (no residual, no pre-norm — caller owns those).

    Returns (out, (k, v)) so callers can populate KV caches.
    kv: precomputed (k, v) (decode path with cache); x_kv: cross-attn source.
    ragged: (lengths, layer, t_max) — S == 1 attention runs through the
    length-aware Pallas decode kernel (kernels/ragged_decode_attention.py)
    instead of the dense masked sdpa. `kv` (and kv_scales) are then a
    segment's STACKED caches, (n, B, T, Hk, Dh), and the kernel reads
    layer `layer` where it lies, rows < t_max (the kv bucket). lengths:
    per-slot (B,) valid-cache-row counts; the caller guarantees row `t`
    is valid iff t < length — this subsumes causal, per-slot-depth AND
    ring-window masking, which is why no q_pos/k_pos reach the kernel.
    kv_scales: (k_scale, v_scale) f32 per row and kv head — `kv` holds
    quantized codes (int8/fp8, cfg.kv_cache_dtype). On the ragged path
    they are stacked like `kv` or the read layer's alone, and the kernel
    fuses the dequant into its kv-block load; every other path (chunked
    prefill S > 1, dense fallback on interpret backends) dequantizes here
    and is the kernel's oracle.
    paged_kv: (block_table, page) — with `ragged`, `kv` is then a stacked
    batchless page POOL ((n, R, Hk, Dh), scales (n, R, Hk)); the kernel
    indexes KV pages through the block table in its own index map, so no
    gathered copy of the cache is materialized.
    """
    dh = cfg.resolved_head_dim
    causal = cfg.causal if causal is None else causal
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    use_rope = not cfg.use_rel_pos_bias
    if use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
    if kv is None:
        assert kv_scales is None, "kv_scales requires a precomputed kv"
        src = x if x_kv is None else x_kv
        k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
        if cfg.qk_norm:
            k = rms_norm(k, p["k_norm"])
        if use_rope:
            k = apply_rope(k, k_pos, cfg.rope_theta)
    else:
        k, v = kv
    use_banded = (banded and isinstance(window, int) and window > 0
                  and kv is None and bias is None and causal
                  and x_kv is None)
    if ragged is not None:
        if not (q.shape[1] == 1 and kv is not None and bias is None
                and causal):
            raise ValueError(
                "the ragged decode path needs S == 1, a kv cache, causal "
                "masking and no bias — stacked caches and paged pools have "
                "no dense (B, T, ...) layout for sdpa")
        from repro.kernels import ops as kops
        lengths, layer, t_max = ragged
        ks, vs = kv_scales if kv_scales is not None else (None, None)
        if paged_kv is not None:
            bt, page = paged_kv
            out = kops.paged_ragged_decode_attn(q, k, v, lengths, bt, ks, vs,
                                                page=page, t_max=t_max,
                                                layer=layer)
        else:
            out = kops.ragged_decode_attn(q, k, v, lengths, ks, vs,
                                          layer=layer, t_max=t_max)
    else:
        assert paged_kv is None, "paged_kv requires the ragged decode path"
        if kv_scales is not None:
            from repro.kernels import quant
            k = quant.dequantize(k, kv_scales[0], x.dtype)
            v = quant.dequantize(v, kv_scales[1], x.dtype)
        if use_banded:
            out = sdpa_local_banded(q, k, v, window=window)
        else:
            out = sdpa(q, k, v, causal=causal, window=window,
                       q_pos=q_pos, k_pos=k_pos, bias=bias)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, (k, v)


def sdpa_local_banded(q, k, v, *, window: int, scale=None):
    """Block-banded sliding-window attention (§Perf lever).

    For a causal window w, token t only attends [t-w+1, t]; computing the
    full (S, S) score matrix and masking wastes S/(2w) x the FLOPs and
    bytes. This computes scores only against the (previous, current)
    w-sized key blocks: (S, 2w) instead of (S, S). Exact same output as
    the masked full path (tested).

    q: (B, S, H, Dh); k, v: (B, S, Hk, Dh); S padded to a multiple of w.
    """
    B, S, H, Dh = q.shape
    Hk = k.shape[2]
    w = window
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    pad = (-S) % w
    if pad:
        zf = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v = zf(q), zf(k), zf(v)
    Sp = S + pad
    nb = Sp // w
    rep = H // Hk
    qb = q.reshape(B, nb, w, H, Dh)
    kb = k.reshape(B, nb, w, Hk, Dh)
    vb = v.reshape(B, nb, w, Hk, Dh)
    # (prev block | current block) keys: (B, nb, 2w, Hk, Dh)
    prev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    k2 = jnp.concatenate([prev, kb], axis=2)
    prev_v = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]],
                             axis=1)
    v2 = jnp.concatenate([prev_v, vb], axis=2)
    qg = qb.reshape(B, nb, w, Hk, rep, Dh)
    s = jnp.einsum("bnqhrd,bnkhd->bnhrqk", qg.astype(jnp.float32),
                   k2.astype(jnp.float32)) * scale     # (B,nb,Hk,rep,w,2w)
    tq = jnp.arange(w)[:, None]
    tk = jnp.arange(2 * w)[None, :]
    rel = (w + tq) - tk
    mask = (rel >= 0) & (rel < w)
    # first block has no previous keys
    first = (tk >= w) & mask
    s0 = jnp.where(first[None, None, None], s[:, :1], NEG_INF)
    if nb > 1:
        srest = jnp.where(mask[None, None, None], s[:, 1:], NEG_INF)
        s = jnp.concatenate([s0, srest], axis=1)
    else:
        s = s0
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnhrqk,bnkhd->bnqhrd", probs,
                     v2.astype(jnp.float32))
    out = out.reshape(B, Sp, H, Dh).astype(q.dtype)
    return out[:, :S] if pad else out


# --------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention
# --------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim
    qr = m.qk_rope_head_dim
    vd = m.v_head_dim
    ks = jax.random.split(key, 8)
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype, in_axis=0),
        "q_a_norm": init_rms_norm(m.q_lora_rank, dtype),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, h, qk + qr), dtype, in_axis=0),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + qr), dtype, in_axis=0),
        "kv_a_norm": init_rms_norm(m.kv_lora_rank, dtype),
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, h, qk), dtype, in_axis=0),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, h, vd), dtype, in_axis=0),
        "wo": dense_init(ks[5], (h, vd, d), dtype, in_axis=0),
    }


def mla_latent(p: dict, cfg: ModelConfig, x: jax.Array, k_pos) -> jax.Array:
    """Project x -> the cached latent [c_kv | k_rope(rotated)]: (B,S,r+qr)."""
    m = cfg.mla
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = rms_norm(c, p["kv_a_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], k_pos, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_rope], axis=-1)


def mla_attention(p: dict, cfg: ModelConfig, x: jax.Array, latent: jax.Array,
                  *, q_pos, k_pos, mesh=None, batch_axes=("data",)) -> jax.Array:
    """Absorbed-matrix MLA: attention runs in the compressed latent space.

    latent: (B, T, r + qr) cache (from mla_latent). This is the TPU-friendly
    "weight absorption" form: W_uk folds into the query, W_uv into the output
    projection, so the KV cache stays (r+qr)-wide regardless of heads.
    """
    m = cfg.mla
    B, S, _ = x.shape
    r = m.kv_lora_rank
    c, k_rope = latent[..., :r], latent[..., r:]           # (B,T,r),(B,T,qr)
    q_a = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype)),
                   p["q_a_norm"])
    q = jnp.einsum("bsr,rhk->bshk", q_a, p["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, q_pos, cfg.rope_theta)
    # absorb W_uk: q_c (B,S,H,r)
    q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"].astype(x.dtype))
    if mesh is not None and cfg.mla_attn_pins:
        ns = jax.sharding.NamedSharding
        from jax.sharding import PartitionSpec as _P
        spec = _P(batch_axes, None, "model", None)
        q_c = jax.lax.with_sharding_constraint(q_c, ns(mesh, spec))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bshr,btr->bhst", q_c.astype(jnp.float32),
                         c.astype(jnp.float32))
              + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = k_pos[None]
    rel = q_pos[:, :, None] - k_pos[:, None, :]
    scores = jnp.where((rel >= 0)[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_c = jnp.einsum("bhst,btr->bshr", probs, c.astype(jnp.float32))
    if mesh is not None and cfg.mla_attn_pins:
        out_c = jax.lax.with_sharding_constraint(
            out_c, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(
                    batch_axes, None, "model", None)))
    out = jnp.einsum("bshr,rhv->bshv", out_c.astype(x.dtype),
                     p["wv_b"].astype(x.dtype))            # absorb W_uv
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))


# --------------------------------------------------------------------------
# gated FFN (SwiGLU / T5 v1.1 gated-GELU)
# --------------------------------------------------------------------------

def init_ffn(key, d: int, f: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w1": dense_init(ks[0], (d, f), dtype, in_axis=0),   # gate
        "w3": dense_init(ks[1], (d, f), dtype, in_axis=0),   # up
        "w2": dense_init(ks[2], (f, d), dtype, in_axis=0),   # down
    }


def ffn_block(p: dict, x: jax.Array, activation: str = "silu") -> jax.Array:
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    h = act(jnp.einsum("...d,df->...f", x, p["w1"].astype(x.dtype)))
    h = h * jnp.einsum("...d,df->...f", x, p["w3"].astype(x.dtype))
    return jnp.einsum("...f,fd->...d", h, p["w2"].astype(x.dtype))
