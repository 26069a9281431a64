"""Plain reference of the served model, in straightforward jax.numpy and
float32 at the highest matmul precision. It imports nothing of the
program and reads only the weights the benchmark made (`weights.py`).

What differs from family to family, the layers of the stack, is the
configuration's model family's (`families/<family>.py` `segments`; the
contract is in `family.py`); this file holds what every family shares,
and the pieces (`rms_norm`, `rope`, `attention`) that families build
their layers from. The stack is widened with AltUp (Baykal et al. 2023,
Alg. 1): the residual stream is K blocks of width d; layer i, counted
across segments, computes on block i % K, and

    x_hat  = P x                       (predict, K x K mixing)
    x_new  = x_hat + g * (L(x[j]) - x_hat[j])   (correct, j = i % K)

The recycled variant repeats the d-wide embedding K times and sums the
blocks before the head; the wide variant reads K*d-wide embedding rows
and normalises the K*d concatenation before the head. The head is the
embedding, tied, or an untied `lm_head` where the weights hold one.
Norm gains are applied as x * (1 + g), the layout the weights are stored
in.

`precision="fp8"` is the control: every matmul weight rounded to
float8 e4m3 with one scale per output channel, activations in bfloat16
with float32 accumulation. It is the step below the configuration's
bfloat16 that a later change might be tempted to take.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 512


def _fp8(w, axis):
    """Round w to float8 e4m3 with one scale per slice along `axis`
    (the contraction axis); returns bfloat16 values."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return ((w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * s).astype(jnp.bfloat16)


class _Math:
    """Matmuls and weight handling of one precision."""

    def __init__(self, precision: str):
        self.low = precision == "fp8"
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)

    def w(self, x, contract_axis):
        return _fp8(x, contract_axis) if self.low else x.astype(jnp.float32)

    def ein(self, spec, a, b):
        if self.low:
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return jnp.einsum(spec, a.astype(jnp.float32),
                          b.astype(jnp.float32), precision=HI)


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def rope(x, theta):
    """x: (L, H, dh); rotate the two halves of each head."""
    L, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mm):
    """Causal GQA. q: (L, H, dh); k, v: (L, Hk, dh). Queries in blocks."""
    L, H, dh = q.shape
    Hk = k.shape[1]
    qg = q.reshape(L, Hk, H // Hk, dh)
    outs = []
    for s0 in range(0, L, Q_BLOCK):
        qb = qg[s0:s0 + Q_BLOCK]
        sc = mm.ein("qhrd,khd->hrqk", qb, k) / math.sqrt(dh)
        qpos = s0 + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(L)[None, :] <= qpos, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(mm.ein("hrqk,khd->qhrd", pr, v))
    return jnp.concatenate(outs, 0).reshape(L, H, dh)


def final_hidden(params, tokens, arch, family, precision="f32"):
    """(L, width) f32 normalised output of the stack for `tokens` (L,),
    and the (possibly rounded) head as (padded vocab, width)."""
    mm = _Math(precision)
    K, d = arch["K"], arch["d_model"]
    emb = mm.w(params["embed"], 1)                      # (Vp, width)
    x = emb[tokens].astype(jnp.float32)
    L = tokens.shape[0]
    x = jnp.broadcast_to(x[:, None, :], (L, K, d)) if arch["recycled"] \
        else x.reshape(L, K, d)
    first = 0                           # global index of a segment's first
    for seg, n, layer in family.segments(params, arch):

        def body(x, per_layer, layer=layer):
            p, i = per_layer
            j = i % K
            onehot = (jnp.arange(K) == j).astype(jnp.float32)
            x_j = jnp.einsum("k,lkd->ld", onehot, x, precision=HI)
            out = layer(x_j, p, arch, mm)
            pm = p["altup_p"].astype(jnp.float32)
            x_hat = jnp.einsum("ij,ljd->lid", pm, x, precision=HI)
            hat_j = jnp.einsum("k,lkd->ld", onehot, x_hat, precision=HI)
            g = p["altup_g"].astype(jnp.float32)
            return x_hat + g[None, :, None] * (out - hat_j)[:, None, :], None

        x, _ = jax.lax.scan(body, x, (seg, jnp.arange(first, first + n)))
        first += n
    x = x.sum(1) if arch["recycled"] else x.reshape(L, K * d)
    head = emb if "lm_head" not in params \
        else mm.w(params["lm_head"], 0).T              # (width, Vp) stored
    return rms_norm(x, params["final_norm"], arch["rms_norm_eps"]), head, mm


def logits(params, tokens, arch, family, precision="f32"):
    """(L, vocab) f32 logits of the whole sequence `tokens` (L,)."""
    x, emb, mm = final_hidden(params, tokens, arch, family, precision)
    return mm.ein("lw,vw->lv", x, emb)[:, :arch["vocab"]]


@partial(jax.jit, static_argnames=("arch_items", "family", "control"))
def gaps(params, tokens, targets, arch_items, family, control=False):
    """Per position of `tokens` (padded, causal, so padding at the end
    changes nothing before it): the reference's best logit minus its
    logit of `targets` (the served token, -1 where none is compared); and
    with `control`, the same gap of the token the fp8 control ranks
    first there. Returns (served_gap, control_gap), 0 where unused. The
    head runs in blocks of rows, so no (L, vocab) array is held."""
    arch = dict(arch_items)
    V = arch["vocab"]
    x, emb, mm = final_hidden(params, tokens, arch, family, "f32")
    if control:
        xl, embl, mml = final_hidden(params, tokens, arch, family, "fp8")
    served, ctl = [], []
    for s0 in range(0, tokens.shape[0], Q_BLOCK):
        ref = mm.ein("lw,vw->lv", x[s0:s0 + Q_BLOCK], emb)[:, :V]
        best = ref.max(-1)
        tg = targets[s0:s0 + Q_BLOCK]
        pick = jnp.take_along_axis(ref, jnp.maximum(tg, 0)[:, None], -1)
        served.append(jnp.where(tg >= 0, best - pick[:, 0], 0.0))
        if control:
            low = mml.ein("lw,vw->lv", xl[s0:s0 + Q_BLOCK], embl)[:, :V]
            first = jnp.argmax(low, -1)[:, None]
            c = jnp.take_along_axis(ref, first, -1)[:, 0]
            ctl.append(jnp.where(tg >= 0, best - c, 0.0))
    served_gap = jnp.concatenate(served)
    return served_gap, (jnp.concatenate(ctl) if control
                        else jnp.zeros_like(served_gap))


NUCLEUS_ROWS = 128      # rows of the head per step of the sampled check


@partial(jax.jit, static_argnames=("arch_items", "family"))
def nucleus(params, tokens, rows, served, temperature, top_p, arch_items,
            family):
    """The sampled check's readings of one request that sampled at
    (`temperature`, `top_p`): `tokens` is its prompt and served tokens
    (padded at the end), `served[i]` the token served after position
    `rows[i]` (-1 where none). At each such position, under the
    reference's distribution p of its logits / temperature:

      above    the mass of the tokens p ranks strictly above the served
               one (the served token lies in the top-p nucleus when this
               is below top_p);
      logq     the served token's log-probability under q, p restricted
               to its nucleus (the smallest run of top tokens whose mass
               reaches top_p) and renormalised; -inf outside it;
      entropy  the entropy of q;
      var      the variance of log q under q.

    A sound sampler draws from q, so over many tokens the sum of
    logq + entropy is near 0 against the square root of the sum of var.
    The head runs in blocks of rows, so no (L, vocab) array is held."""
    arch = dict(arch_items)
    V = arch["vocab"]
    x, emb, mm = final_hidden(params, tokens, arch, family, "f32")
    R, w = rows.shape[0], x.shape[1]
    nb = -(-R // NUCLEUS_ROWS)
    pad = nb * NUCLEUS_ROWS - R
    xb = jnp.pad(x[rows], ((0, pad), (0, 0))).reshape(nb, NUCLEUS_ROWS, w)
    tb = jnp.pad(served, (0, pad), constant_values=-1).reshape(
        nb, NUCLEUS_ROWS)

    def block(args):
        xr, tg = args
        z = mm.ein("lw,vw->lv", xr, emb)[:, :V] / temperature
        logp = jax.nn.log_softmax(z, axis=-1)
        p = jnp.exp(logp)
        t = jnp.maximum(tg, 0)[:, None]
        z_t = jnp.take_along_axis(z, t, -1)
        above = jnp.sum(jnp.where(z > z_t, p, 0.0), -1)
        srt = jnp.sort(p, axis=-1)[:, ::-1]
        before = jnp.cumsum(srt, axis=-1) - srt
        n_keep = jnp.sum(before < top_p, axis=-1)
        p_th = jnp.take_along_axis(srt, (n_keep - 1)[:, None], -1)
        keep = p >= p_th
        logq = logp - jnp.log(jnp.sum(jnp.where(keep, p, 0.0), -1,
                                      keepdims=True))
        q = jnp.where(keep, jnp.exp(logq), 0.0)
        lq = jnp.where(keep, logq, 0.0)
        ent = -jnp.sum(q * lq, -1)
        var = jnp.sum(q * lq * lq, -1) - ent * ent
        lq_t = jnp.where(jnp.take_along_axis(keep, t, -1),
                         jnp.take_along_axis(logq, t, -1), -jnp.inf)[:, 0]
        return above, lq_t, ent, var

    outs = jax.lax.map(block, (xb, tb))
    return tuple(o.reshape(-1)[:R] for o in outs)
