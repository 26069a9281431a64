"""Dense pre-norm decoder (Qwen3 / Granite 3 style): RMSNorm, GQA with
rotary embeddings on the two halves of each head, optional per-head
RMSNorm of queries and keys, SwiGLU MLP, the embedding tied to the output
head, every layer in one stacked segment `seg0`. The family contract is
in `benchcore/family.py`."""
from __future__ import annotations

import jax

from benchcore import family, models, weights
from benchcore.reference import attention, rms_norm, rope

# per-head RMSNorm of queries and keys, by the source's model_type
QK_NORM = {"qwen3": True, "granite": False}
# keys of the file's `model` that `reduced` may cut: the ModelConfig
# attribute each sets
REDUCIBLE = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size"}


def _head_dim(m: dict) -> int:
    return int(m.get("head_dim") or
               m["hidden_size"] // m["num_attention_heads"])


def program_config(config: dict):
    m = config["model"]
    cfg = family.preset(config, REDUCIBLE)
    want = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "resolved_head_dim": _head_dim(m),
            "d_ff": m["intermediate_size"], "vocab_size": m["vocab_size"],
            "rope_theta": float(m["rope_theta"]),
            "logical_norm_eps": float(m["rms_norm_eps"]),
            "tie_embeddings": bool(m["tie_word_embeddings"]),
            "qk_norm": QK_NORM[m["model_type"]],
            "dtype": m["torch_dtype"], "param_dtype": m["torch_dtype"]}
    return cfg, want


def layout(config: dict) -> dict:
    m, alt = config["model"], config["altup"]
    dtype = m["torch_dtype"]
    L, d = int(m["num_hidden_layers"]), int(m["hidden_size"])
    H, Hk = int(m["num_attention_heads"]), int(m["num_key_value_heads"])
    dh, F, K = _head_dim(m), int(m["intermediate_size"]), int(alt["K"])
    width = d if (K == 1 or alt["recycled"]) else K * d
    leaves = {
        "embed": ((weights.padded_vocab(int(m["vocab_size"])), width),
                  dtype, "embed"),
        "final_norm": ((width,), dtype, "norm"),
        "seg0/ln_attn": ((L, d), dtype, "norm"),
        "seg0/attn/wq": ((L, d, H, dh), dtype, d),
        "seg0/attn/wk": ((L, d, Hk, dh), dtype, d),
        "seg0/attn/wv": ((L, d, Hk, dh), dtype, d),
        "seg0/attn/wo": ((L, H, dh, d), dtype, H * dh),
        "seg0/ln_ffn": ((L, d), dtype, "norm"),
        "seg0/ffn/w1": ((L, d, F), dtype, d),
        "seg0/ffn/w3": ((L, d, F), dtype, d),
        "seg0/ffn/w2": ((L, F, d), dtype, F),
    }
    if QK_NORM[m["model_type"]]:
        leaves["seg0/attn/q_norm"] = ((L, dh), dtype, "norm")
        leaves["seg0/attn/k_norm"] = ((L, dh), dtype, "norm")
    if K > 1:
        leaves["seg0/altup_p"] = ((L, K, K), "float32", "altup_p")
        leaves["seg0/altup_g"] = ((L, K), "float32", "altup_g")
    return leaves


def arch(config: dict) -> tuple:
    m = config["model"]
    return tuple(sorted({
        "K": int(config["altup"]["K"]),
        "recycled": bool(config["altup"]["recycled"]),
        "d_model": int(m["hidden_size"]),
        "vocab": int(m["vocab_size"]),
        "n_layers": int(m["num_hidden_layers"]),
        "rms_norm_eps": float(m["rms_norm_eps"]),
        "rope_theta": float(m["rope_theta"]),
        "qk_norm": QK_NORM[m["model_type"]],
    }.items()))


def layer(x, p, arch, mm):
    """One width-d pre-norm layer with its residuals. x: (L, d) f32."""
    eps = arch["rms_norm_eps"]
    h = rms_norm(x, p["ln_attn"], eps)
    a = p["attn"]
    q = mm.ein("ld,dhk->lhk", h, mm.w(a["wq"], 0))
    k = mm.ein("ld,dhk->lhk", h, mm.w(a["wk"], 0))
    v = mm.ein("ld,dhk->lhk", h, mm.w(a["wv"], 0))
    if arch["qk_norm"]:
        q = rms_norm(q, a["q_norm"], eps)
        k = rms_norm(k, a["k_norm"], eps)
    q, k = rope(q, arch["rope_theta"]), rope(k, arch["rope_theta"])
    o = attention(q, k, v, mm)
    x = x + mm.ein("lhk,hkd->ld", o, mm.w(a["wo"], (0, 1)))
    h = rms_norm(x, p["ln_ffn"], eps)
    f = p["ffn"]
    u = jax.nn.silu(mm.ein("ld,df->lf", h, mm.w(f["w1"], 0))) \
        * mm.ein("ld,df->lf", h, mm.w(f["w3"], 0))
    return x + mm.ein("lf,fd->ld", u, mm.w(f["w2"], 0))


def segments(params, arch):
    return [(params["seg0"], arch["n_layers"], layer)]


def shape(config: dict) -> models.Shape:
    m = config["model"]
    d, H = int(m["hidden_size"]), int(m["num_attention_heads"])
    Hk, dh = int(m["num_key_value_heads"]), _head_dim(m)
    one = models.Layer(
        matmul_params=d * H * dh * 2 + d * Hk * dh * 2
        + 3 * d * int(m["intermediate_size"]),
        attn_flops_per_key=4 * H * dh, attn_io=2 * H * dh,
        kv_values=2 * Hk * dh, kv_scales=2 * Hk, window=0)
    return models.Shape(d_model=d, vocab=int(m["vocab_size"]),
                        altup_k=int(config["altup"]["K"]),
                        recycled=bool(config["altup"]["recycled"]),
                        layers=(one,) * int(m["num_hidden_layers"]))
