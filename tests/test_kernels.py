"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
ref.py, executed in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.altup_fused import altup_predict_correct as altup_raw
from repro.kernels.flash_attention import flash_attention as fa_raw
from repro.kernels.ragged_decode_attention import (
    ragged_decode_attention as ragged_raw)
from repro.kernels.rwkv6_scan import rwkv6_wkv as rwkv_raw

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T,K,d,bt,bd", [
    (32, 2, 128, 8, 128),
    (64, 4, 256, 32, 64),
    (128, 2, 512, 128, 512),
    (16, 3, 64, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_altup_fused_sweep(T, K, d, bt, bd, dtype):
    ks = jax.random.split(KEY, 5)
    xw = jax.random.normal(ks[0], (T, K, d), dtype)
    xt = jax.random.normal(ks[1], (T, d), dtype)
    p = jax.random.normal(ks[2], (K, K), jnp.float32)
    g = jax.random.normal(ks[3], (K,), jnp.float32)
    sel = (jnp.arange(K) == (K - 1)).astype(jnp.float32)
    got = altup_raw(xw, xt, sel, p, g, block_t=bt, block_d=bd,
                    interpret=True)
    want = ref.altup_predict_correct_ref(xw, xt, sel, p, g)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("S,dh,bq,bk,causal,window", [
    (128, 64, 64, 64, True, 0),
    (128, 64, 32, 64, True, 48),
    (256, 128, 128, 128, True, 0),
    (64, 32, 64, 64, False, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, dh, bq, bk, causal, window, dtype):
    BH = 3
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (BH, S, dh), dtype)
    k = jax.random.normal(ks[1], (BH, S, dh), dtype)
    v = jax.random.normal(ks[2], (BH, S, dh), dtype)
    got = fa_raw(q, k, v, causal=causal, window=window, block_q=bq,
                 block_k=bk, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_gqa_wrapper():
    B, S, H, Hk, dh = 2, 128, 8, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hk, dh))
    v = jax.random.normal(ks[2], (B, S, Hk, dh))
    got = ops.mha_flash(q, k, v, causal=True, block_q=64, block_k=64)
    kx = jnp.repeat(k, H // Hk, axis=2)
    vx = jnp.repeat(v, H // Hk, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, S, dh)
    want = ref.attention_ref(fold(q), fold(kx), fold(vx), causal=True)
    want = want.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# ragged decode-attention kernel (serving hot path)
# --------------------------------------------------------------------------

def _slot_lengths(B, T, seed=0):
    """Per-slot fill depths including an EMPTY and a FULL slot."""
    lens = np.random.default_rng(seed).integers(1, T + 1, B)
    lens[0] = 0
    lens[-1] = T
    return jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("B,T,Hk,rep,dh,bk", [
    (4, 96, 2, 1, 32, 32),      # no grouping (H == Hk)
    (4, 96, 2, 2, 32, 32),      # GQA 2:1
    (3, 128, 1, 4, 64, 64),     # GQA 4:1, single kv head
    (5, 100, 2, 2, 16, 32),     # odd T % block_k
    (2, 40, 2, 3, 16, 64),      # block_k > T (single clamped block)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_decode_kernel_sweep(B, T, Hk, rep, dh, bk, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hk, rep, dh), dtype)
    k = jax.random.normal(ks[1], (B, T, Hk, dh), dtype)
    v = jax.random.normal(ks[2], (B, T, Hk, dh), dtype)
    lens = _slot_lengths(B, T, seed=B)
    got = ragged_raw(q, k, v, lens, block_k=bk, interpret=True)
    want = ref.ragged_decode_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("H,Hk", [(4, 4), (4, 2), (8, 2)])
def test_ragged_wrapper_matches_dense_sdpa(H, Hk):
    """The model-layout wrapper == layers.sdpa with per-slot causal
    masking — the dense fallback oracle the serving path dispatches to."""
    from repro.models.layers import sdpa
    B, T, dh = 4, 64, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh))
    k = jax.random.normal(ks[1], (B, T, Hk, dh))
    v = jax.random.normal(ks[2], (B, T, Hk, dh))
    lens = jnp.asarray([1, 17, 40, 64], jnp.int32)
    got = ops.ragged_decode_attn(q, k, v, lens, block_k=32)
    q_pos = (lens - 1)[:, None]
    want = sdpa(q, k, v, causal=True, window=None, q_pos=q_pos,
                k_pos=jnp.arange(T))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ragged_kernel_ring_window_wraparound():
    """Sliding-window decode on a WRAPPED ring cache == dense windowed
    attention over the full linear history. The ring needs no index remap
    in the kernel: attention is permutation-invariant over the key set,
    and a depth-p ring holds exactly the last min(p+1, W) positions."""
    from repro.models.layers import sdpa
    B, W, Hk, rep, dh = 3, 16, 2, 2, 16
    Tfull = 40
    pos = jnp.asarray([5, 17, 39], jnp.int32)      # pre-, just-, deep-wrap
    ks = jax.random.split(KEY, 3)
    kfull = jax.random.normal(ks[0], (B, Tfull, Hk, dh))
    vfull = jax.random.normal(ks[1], (B, Tfull, Hk, dh))
    q = jax.random.normal(ks[2], (B, 1, Hk * rep, dh))
    # build the ring the decode path builds: row t%W holds position t
    kr = jnp.zeros((B, W, Hk, dh))
    vr = jnp.zeros((B, W, Hk, dh))
    for b in range(B):
        for t in range(int(pos[b]) + 1):
            kr = kr.at[b, t % W].set(kfull[b, t])
            vr = vr.at[b, t % W].set(vfull[b, t])
    lens = jnp.minimum(pos + 1, W)
    got = ops.ragged_decode_attn(q, kr, vr, lens, block_k=8)
    want = sdpa(q, kfull, vfull, causal=True, window=W,
                q_pos=pos[:, None], k_pos=jnp.arange(Tfull))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["ragged", "paged"])
def test_decode_kernels_read_a_layer_of_the_stack(kind, dtype):
    """A segment's stacked caches read at `layer` == the 4-D call on that
    layer's caches, bitwise, at the first, a middle and the last layer —
    the stack is indexed in the kernel's index maps, not sliced. Also
    with stacked codes beside the read layer's own scales, and (ragged)
    with a read bound t_max under T beside the 4-D call on rows < t_max:
    the two forms the decode loop hands the kernels."""
    from repro.kernels.ragged_decode_attention import (
        paged_ragged_decode_attention as paged_raw)
    n, B, T, Hk, rep, dh, page = 5, 3, 32, 2, 2, 16, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hk, rep, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (n, B, T, Hk, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (n, B, T, Hk, dh), jnp.bfloat16)
    scales = {}
    if dtype == "int8":
        k, v, ksc, vsc = _quant_cache(k, v, "int8")
        scales = {"k_scale": ksc, "v_scale": vsc}
    lens = jnp.asarray([0, 13, T], jnp.int32)
    if kind == "paged":
        # pool rows in a permuted page order, one table for every layer
        perm = np.random.default_rng(5).permutation(B * T // page)
        bt = jnp.asarray(perm.reshape(B, T // page), jnp.int32)
        pool = lambda t: jnp.zeros((n, B * T) + t.shape[3:], t.dtype).at[
            :, (bt[:, :, None] * page + jnp.arange(page)).reshape(B, T)
        ].set(t)
        k, v = pool(k), pool(v)
        scales = {name: pool(s) for name, s in scales.items()}
        call = lambda kk, vv, **kw: paged_raw(q, kk, vv, lens, bt, page=page,
                                              t_max=T, interpret=True, **kw)
    else:
        call = lambda kk, vv, **kw: ragged_raw(q, kk, vv, lens, block_k=8,
                                               interpret=True, **kw)
    for layer in (0, n // 2, n - 1):
        one = {name: s[layer] for name, s in scales.items()}
        want = np.asarray(call(k[layer], v[layer], **one), np.float32)
        got = call(k, v, layer=layer, **scales)
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)
        if scales:
            got = call(k, v, layer=layer, **one)
            np.testing.assert_array_equal(np.asarray(got, np.float32), want)
        if kind == "ragged":
            t_max, short = 16, jnp.minimum(lens, 16)
            rows = {name: s[:, :t_max] for name, s in one.items()}
            want = ragged_raw(q, k[layer][:, :t_max], v[layer][:, :t_max],
                              short, block_k=8, interpret=True, **rows)
            got = ragged_raw(q, k, v, short, layer=layer, t_max=t_max,
                             block_k=8, interpret=True, **rows)
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


# --------------------------------------------------------------------------
# quantized KV caches: fused-dequant kernels vs dense dequant oracles
# --------------------------------------------------------------------------

def _quant_cache(k, v, kind):
    """Quantize a (B, T, Hk, Dh) slot cache the way decode.py writes it:
    per-(position, head) scales over the head dim."""
    from repro.kernels import quant
    spec = quant.resolve_kv_spec(kind, k.dtype)
    kq, ksc = quant.quantize(k, spec)
    vq, vsc = quant.quantize(v, spec)
    return kq, vq, ksc, vsc


@pytest.mark.parametrize("kind", ["int8", "fp8"])
@pytest.mark.parametrize("B,T,Hk,rep,dh,bk", [
    (4, 96, 2, 2, 32, 32),      # GQA 2:1
    (3, 128, 1, 4, 64, 64),     # GQA 4:1, single kv head
    (5, 100, 2, 2, 16, 32),     # odd T % block_k
])
def test_ragged_decode_kernel_quant_sweep(kind, B, T, Hk, rep, dh, bk):
    """Fused in-kernel dequant == dense dequant-then-attend oracle, for
    int8 codes and fp8 (e4m3-grid) codes, through the same clamped
    scalar-prefetch index maps."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hk, rep, dh))
    k = jax.random.normal(ks[1], (B, T, Hk, dh))
    v = jax.random.normal(ks[2], (B, T, Hk, dh))
    kq, vq, ksc, vsc = _quant_cache(k, v, kind)
    lens = _slot_lengths(B, T, seed=B)
    got = ragged_raw(q, kq, vq, lens, k_scale=ksc, v_scale=vsc,
                     block_k=bk, interpret=True)
    want = ref.ragged_decode_quant_ref(q, kq, vq, ksc, vsc, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ragged_quant_wrapper_and_tolerance_vs_fp32():
    """ops-layer quantized dispatch: (a) exact vs the dense dequant
    oracle, (b) within the documented int8 tolerance of the UNquantized
    fp32 attention (docs/serving.md: per-head-row amax int8 keeps decode
    attention within ~1e-2 of fp32)."""
    from repro.models.layers import sdpa
    B, T, H, Hk, dh = 4, 64, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh))
    k = jax.random.normal(ks[1], (B, T, Hk, dh))
    v = jax.random.normal(ks[2], (B, T, Hk, dh))
    kq, vq, ksc, vsc = _quant_cache(k, v, "int8")
    lens = jnp.asarray([1, 17, 40, 64], jnp.int32)
    got = ops.ragged_decode_attn(q, kq, vq, lens, ksc, vsc, block_k=32)
    rep = H // Hk
    want_q = ref.ragged_decode_quant_ref(
        q[:, 0].reshape(B, Hk, rep, dh), kq, vq, ksc, vsc, lens
    ).reshape(B, 1, H, dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_q),
                               rtol=2e-5, atol=2e-5)
    want_fp32 = sdpa(q, k, v, causal=True, window=None,
                     q_pos=(lens - 1)[:, None], k_pos=jnp.arange(T))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_fp32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("kind", ["int8", "fp8"])
@pytest.mark.parametrize("causal,window,bq,bk", [
    (True, 0, 64, 64),
    (True, 48, 32, 64),
    (False, 0, 64, 64),
])
def test_flash_attention_quant_sweep(kind, causal, window, bq, bk):
    """Quantized prefill flash kernel: fused dequant through the
    block-skip remapped index maps == dense dequant oracle."""
    from repro.kernels import quant
    BH, S, dh = 3, 128, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (BH, S, dh))
    k = jax.random.normal(ks[1], (BH, S, dh))
    v = jax.random.normal(ks[2], (BH, S, dh))
    spec = quant.resolve_kv_spec(kind, k.dtype)
    kq, ksc = quant.quantize(k, spec)
    vq, vsc = quant.quantize(v, spec)
    got = fa_raw(q, kq, vq, k_scale=ksc, v_scale=vsc, causal=causal,
                 window=window, block_q=bq, block_k=bk, interpret=True)
    want = ref.attention_quant_ref(q, kq, vq, ksc, vsc, causal=causal,
                                   window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_quant_gqa_wrapper():
    """ops.mha_flash with quantized k/v: scales ride the GQA expansion."""
    B, S, H, Hk, dh = 2, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hk, dh))
    v = jax.random.normal(ks[2], (B, S, Hk, dh))
    kq, vq, ksc, vsc = _quant_cache(k, v, "int8")
    got = ops.mha_flash(q, kq, vq, ksc, vsc, causal=True,
                        block_q=64, block_k=64)
    from repro.kernels import quant
    kd = quant.dequantize(kq, ksc, jnp.float32)
    vd = quant.dequantize(vq, vsc, jnp.float32)
    want = ops.mha_flash(q, kd, vd, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_quant_software_e4m3_matches_native_cast():
    """round_e4m3 (the simulated-fp8 fallback for backends without the
    dtype) lands on the e4m3 grid and agrees with the native
    float8_e4m3fn cast to within one grid step. (Bit-exact agreement is
    impossible to demand: XLA's CPU f32->f8 cast double-rounds through
    an f16 intermediate, flipping ~0.3% of near-tie values by one ulp;
    round_e4m3 single-rounds, which is the truer e4m3.)"""
    from repro.kernels import quant
    yv = jnp.concatenate([
        jax.random.normal(KEY, (4096,)) * 100.0,     # normals
        jax.random.normal(jax.random.fold_in(KEY, 1), (1024,)) * 1e-3,
        jnp.asarray([0.0, 448.0, -448.0, 460.0, -460.0, 2.0 ** -9,
                     2.0 ** -10, -2.0 ** -6]),
    ])
    got = np.asarray(quant.round_e4m3(yv))
    want = np.asarray(yv.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    # every software-rounded value is itself on the e4m3 grid
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(got).astype(jnp.float8_e4m3fn)
                   .astype(jnp.float32)), got)
    # and within one grid step (|y|/8, floored at the subnormal step)
    ulp = np.maximum(np.abs(np.asarray(yv)) / 8.0, 2.0 ** -9)
    assert (np.abs(got - want) <= ulp + 1e-12).all()
    # exact wherever the native cast did not hit a double-rounding tie
    assert (got == want).mean() > 0.99


@pytest.mark.parametrize("kind,bound", [("int8", 1.0 / 127.0),
                                        ("fp8", 1.0 / 8.0)])
def test_quant_roundtrip_error_bound(kind, bound):
    """Dequant(quantize(x)) error is bounded by the per-row scale: half
    an int8 step, or the e4m3 relative step (2^-3) of the row amax."""
    from repro.kernels import quant
    x = jax.random.normal(KEY, (5, 33, 3, 24))
    spec = quant.resolve_kv_spec(kind, x.dtype)
    codes, scale = quant.quantize(x, spec)
    back = quant.dequantize(codes, scale, jnp.float32)
    amax = np.asarray(jnp.max(jnp.abs(x), axis=-1, keepdims=True))
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= bound * amax + 1e-6).all(), err.max()


def test_quant_float_kinds_are_pure_casts():
    """float32/bf16/auto KVQuantSpecs quantize to a plain dtype cast with
    no scale tensor — the bit-identical legacy storage path."""
    from repro.kernels import quant
    x = jax.random.normal(KEY, (4, 8))
    for name, want_dtype in [("auto", jnp.float32),
                             ("float32", jnp.float32),
                             ("bf16", jnp.bfloat16)]:
        spec = quant.resolve_kv_spec(name, jnp.float32)
        assert not spec.quantized
        codes, scale = quant.quantize(x, spec)
        assert scale is None and codes.dtype == want_dtype
        if want_dtype == jnp.float32:
            np.testing.assert_array_equal(np.asarray(codes), np.asarray(x))


def test_quant_shared_with_gradient_compression():
    """optim/compression's int8 path goes through the same kernels/quant
    scale + rounding helpers (one copy of the logic): deterministic parts
    agree and the stochastic round stays within one step."""
    from repro.kernels import quant
    from repro.optim.compression import int8_dequantize, int8_quantize
    g = jax.random.normal(KEY, (257,)) * 3.0
    q, scale = int8_quantize(g, jax.random.PRNGKey(3))
    want_scale = quant.amax_scale(g, quant.INT8_QMAX, axis=None)
    np.testing.assert_allclose(float(scale), float(want_scale))
    back = int8_dequantize(q, scale, jnp.float32)
    assert np.abs(np.asarray(back - g)).max() <= float(scale) * 1.0 + 1e-6


def test_flash_block_skip_boundaries():
    """Block-skipping (causal + window pl.when grids) is output-invariant
    across block shapes, including windows that cross block bounds."""
    BH, S, dh = 2, 192, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (BH, S, dh))
    k = jax.random.normal(ks[1], (BH, S, dh))
    v = jax.random.normal(ks[2], (BH, S, dh))
    for causal, window in [(True, 0), (True, 40), (True, 64), (False, 0)]:
        want = ref.attention_ref(q, k, v, causal=causal, window=window)
        for bq, bk in [(32, 64), (64, 32), (192, 64)]:
            got = fa_raw(q, k, v, causal=causal, window=window,
                         block_q=bq, block_k=bk, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
                err_msg=f"causal={causal} window={window} bq={bq} bk={bk}")


def test_decode_altup_fused_batched_single_token():
    """The decode-loop fused predict+correct wrapper on (B, S, K, d)
    streams (S=1 decode tick, S=chunk prefill) vs the unfused oracle."""
    for B, S, K, d in [(3, 1, 2, 64), (2, 4, 3, 32), (8, 1, 4, 128)]:
        ks = jax.random.split(jax.random.fold_in(KEY, B * S), 5)
        xw = jax.random.normal(ks[0], (B, S, K, d))
        xt = jax.random.normal(ks[1], (B, S, d))
        p = jax.random.normal(ks[2], (K, K), jnp.float32)
        g = jax.random.normal(ks[3], (K,), jnp.float32)
        sel = (jnp.arange(K) == 0).astype(jnp.float32)
        got = ops.decode_altup_predict_correct(xw, xt, sel, p, g)
        want = ref.altup_predict_correct_ref(
            xw.reshape(B * S, K, d), xt.reshape(B * S, d), sel, p, g
        ).reshape(B, S, K, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,Dh,chunk", [(32, 16, 8), (64, 32, 16),
                                        (48, 64, 16), (8, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_kernel_sweep(S, Dh, chunk, dtype):
    BH = 4
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (BH, S, Dh), dtype)
    k = jax.random.normal(ks[1], (BH, S, Dh), dtype)
    v = jax.random.normal(ks[2], (BH, S, Dh), dtype)
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (BH, S, Dh))) * 0.5
         + 0.5).astype(dtype)
    u = jax.random.normal(ks[4], (BH, Dh), jnp.float32)
    got_o, got_s = rwkv_raw(r, k, v, w, u, chunk=chunk, interpret=True)
    want_o, want_s = ref.rwkv6_wkv_ref(r, k, v, w, u)
    t = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_o, np.float32),
                               np.asarray(want_o, np.float32), **t)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), **t)


def test_chunked_wkv_matches_scan():
    """The model's matmul-form WKV (used for train/prefill) vs the naive
    recurrence."""
    from repro.models.rwkv import wkv_chunked, wkv_scan
    B, S, H, Dh = 2, 50, 3, 16
    ks = jax.random.split(KEY, 6)
    r, k, v = [jax.random.normal(ks[i], (B, S, H, Dh)) for i in range(3)]
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, Dh)) * 1.5 - 2))
    u = jax.random.normal(ks[4], (H, Dh))
    s0 = jax.random.normal(ks[5], (B, H, Dh, Dh))
    o1, f1 = wkv_scan(r, k, v, w, u, s0)
    o2, f2 = wkv_chunked(r, k, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-4,
                               atol=1e-4)


def test_ssd_chunked_matches_naive():
    """Chunked SSD vs direct per-step recurrence."""
    from repro.models.ssm import ssd_scan
    B, S, H, Dh, N = 2, 37, 2, 8, 4
    ks = jax.random.split(KEY, 6)
    xh = jax.random.normal(ks[0], (B, S, H, Dh))
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A = jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    D = jax.random.normal(ks[5], (H,))
    s0 = jnp.zeros((B, H, Dh, N))
    got_y, got_s = ssd_scan(xh, Bm, Cm, dt, A, D, s0, chunk=8)

    # naive recurrence
    y = np.zeros((B, S, H, Dh), np.float32)
    s = np.zeros((B, H, Dh, N), np.float32)
    xh_, Bm_, Cm_, dt_ = map(np.asarray, (xh, Bm, Cm, dt))
    A_, D_ = np.asarray(A), np.asarray(D)
    for t in range(S):
        a = np.exp(-dt_[:, t] * A_[None])                  # (B, H)
        inc = (dt_[:, t][..., None, None] * xh_[:, t][..., None]
               * Bm_[:, t][:, None, None, :])
        s = a[..., None, None] * s + inc
        y[:, t] = np.einsum("bhdn,bn->bhd", s, Cm_[:, t]) \
            + D_[None, :, None] * xh_[:, t]
    np.testing.assert_allclose(np.asarray(got_y), y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_s), s, rtol=2e-4, atol=2e-4)
