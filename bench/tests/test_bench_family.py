"""The dense decoder's family file reproduces the yardstick the harness
had before families: the same weight layout, weights, FLOP and byte
models and reference logits, pinned here as numbers. And the generic
reference: a stack split into segments, an untied head, and a `reduced`
key that reaches the program."""
import copy
import hashlib
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchcore import driver, models, reference, spec  # noqa: E402
from benchcore import traffic, weights  # noqa: E402

DENSE = spec.load_family("dense_decoder", BENCH)
TINY = json.loads((BENCH / "tests" / "data" / "tiny" / "configs"
                   / "tiny-qwen3.json").read_text())


def _config(name):
    cell = next(w["name"] for w in spec.load_benchmark()["workloads"]
                if w["config"] == name)
    return spec.resolve(cell).config


def _seg(L, d, H, Hk, dh, F, qk_norm):
    bf = "bfloat16"
    rows = [("seg0/ln_attn", (L, d), bf, "norm"),
            ("seg0/attn/wq", (L, d, H, dh), bf, d),
            ("seg0/attn/wk", (L, d, Hk, dh), bf, d),
            ("seg0/attn/wv", (L, d, Hk, dh), bf, d),
            ("seg0/attn/wo", (L, H, dh, d), bf, H * dh),
            ("seg0/ln_ffn", (L, d), bf, "norm"),
            ("seg0/ffn/w1", (L, d, F), bf, d),
            ("seg0/ffn/w3", (L, d, F), bf, d),
            ("seg0/ffn/w2", (L, F, d), bf, F)]
    if qk_norm:
        rows += [("seg0/attn/q_norm", (L, dh), bf, "norm"),
                 ("seg0/attn/k_norm", (L, dh), bf, "norm")]
    return rows + [("seg0/altup_p", (L, 2, 2), "float32", "altup_p"),
                   ("seg0/altup_g", (L, 2), "float32", "altup_g")]


# the harness's weight layout before families, leaf by leaf in seed order
PINNED_LAYOUT = {
    "qwen3-0.6b-altup2": [
        ("embed", (152064, 1024), "bfloat16", "embed"),
        ("final_norm", (1024,), "bfloat16", "norm"),
        *_seg(28, 1024, 16, 8, 128, 3072, True)],
    "granite-3-2b-altup2": [
        ("embed", (49408, 4096), "bfloat16", "embed"),
        ("final_norm", (4096,), "bfloat16", "norm"),
        *_seg(40, 2048, 32, 8, 64, 8192, False)],
}
# its FLOP and byte models at the configurations' shapes
SPANS = ((0, 1), (40, 8), (1000, 1), (4095, 1), (0, 512), (300, 64))
LENGTHS = ([0, 1, 17, 100, 999, 1537, 2048, 4095], [4096] * 8, [0] * 7 + [33])
KERNEL_CASES = [(ln, kv, page) for ln in LENGTHS for kv in ("bf16", "int8")
                for page in (0, 16)]
ALTUP_ROWS = (1, 8, 64, 512)
PINNED_COSTS = {
    "qwen3-0.6b-altup2": {
        "matmul_params": 595984384,
        "span_flops": [1192599552.0, 9620619264.0, 1421975552.0,
                       2131894272.0, 640617021440.0, 81192812544.0],
        "attn": [(72065024.0, 36098080.0), (72065024.0, 36380736.0),
                 (72065024.0, 18644832.0), (72065024.0, 18794560.0),
                 (268435456.0, 134283296.0), (268435456.0, 134291520.0),
                 (268435456.0, 69271584.0), (268435456.0, 69279808.0),
                 (270336.0, 200736.0), (270336.0, 270400.0),
                 (270336.0, 135264.0), (270336.0, 175168.0)],
        "altup": [(14336.0, 10272), (114688.0, 81952), (917504.0, 655392),
                  (7340032.0, 5242912)]},
    "granite-3-2b-altup2": {
        "matmul_params": 2634035200,
        "span_flops": [5269544960.0, 42270392320.0, 5597224960.0,
                       6611394560.0, 2740872806400.0, 344202936320.0],
        "attn": [(72065024.0, 18081824.0), (72065024.0, 18227264.0),
                 (72065024.0, 9636704.0), (72065024.0, 9717824.0),
                 (268435456.0, 67174432.0), (268435456.0, 67182656.0),
                 (268435456.0, 35717152.0), (268435456.0, 35725376.0),
                 (270336.0, 133152.0), (270336.0, 172096.0),
                 (270336.0, 101472.0), (270336.0, 126016.0)],
        "altup": [(28672.0, 20512), (229376.0, 163872),
                  (1835008.0, 1310752), (14680064.0, 10485792)]},
}
# sha256 of the tiny config's weights at seed 11 (path, then bytes, leaf
# by leaf in path order), and of its float32 reference logits over 40
# tokens drawn from the seed
TINY_WEIGHTS_SHA256 = \
    "c40652d697e66400e4d0704f12e8d50521a86bdc52e3da176ba7fa844d974a28"
TINY_LOGITS_SHA256 = \
    "7d244354a01ff47a94517048c2b6fc6b37e6dda10a4e13e9e58edcc84744094d"
TINY_FP8_LOGITS_SHA256 = \
    "415343be53d24b060aa6140636ace32a1e7ac29293fe50b8846f4ce83277f196"


@pytest.mark.parametrize("name", list(PINNED_LAYOUT))
def test_layout_is_the_pinned_one(name):
    got = [(p, tuple(shp), dt, kind)
           for p, (shp, dt, kind) in DENSE.layout(_config(name)).items()]
    assert got == PINNED_LAYOUT[name]


@pytest.mark.parametrize("name", list(PINNED_COSTS))
def test_costs_are_the_pinned_ones(name):
    s = DENSE.shape(_config(name))
    want = PINNED_COSTS[name]
    assert models.matmul_params(s) == want["matmul_params"]
    assert [models.span_flops(s, p, n) for p, n in SPANS] == \
        pytest.approx(want["span_flops"], rel=1e-12)
    for (ln, kv, page), (f, b) in zip(KERNEL_CASES, want["attn"]):
        assert models.attn_kernel_cost(s, ln, T=4096, kv_dtype=kv,
                                       page=page) == \
            pytest.approx((f, b), rel=1e-12)
    for rows, (f, b) in zip(ALTUP_ROWS, want["altup"]):
        assert models.altup_kernel_cost(s, rows) == \
            pytest.approx((f, b), rel=1e-12)


def _tiny_params(config=TINY, seed=11):
    return weights.make_params(DENSE.layout(config),
                               int(config["model"]["vocab_size"]), seed)


def _tiny_tokens():
    return traffic.rng_for(11, 0).integers(0, 512, 40).astype(np.int32)


def _logits(params, fam, precision="f32", config=TINY):
    import jax.numpy as jnp
    return np.asarray(reference.logits(
        params, jnp.asarray(_tiny_tokens()), dict(DENSE.arch(config)), fam,
        precision))


def test_tiny_weights_are_bitwise_the_pinned_ones():
    import jax
    flat = jax.tree_util.tree_flatten_with_path(_tiny_params())[0]
    h = hashlib.sha256()
    for k, v in sorted((jax.tree_util.keystr(k), v) for k, v in flat):
        h.update(k.encode())
        h.update(np.asarray(v).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


@pytest.mark.parametrize("precision,sha", [
    ("f32", TINY_LOGITS_SHA256), ("fp8", TINY_FP8_LOGITS_SHA256)])
def test_tiny_reference_logits_are_the_pinned_ones(precision, sha):
    lg = _logits(_tiny_params(), DENSE, precision)
    assert hashlib.sha256(lg.tobytes()).hexdigest() == sha


def _split_family(at):
    """The dense family with its stack split into two segments at
    layer `at`."""
    import jax
    fam = types.ModuleType(f"split_at_{at}")

    def segments(params, arch):
        (seg, n, layer), = DENSE.segments(params, arch)
        return [(jax.tree_util.tree_map(lambda a: a[:at], seg), at, layer),
                (jax.tree_util.tree_map(lambda a: a[at:], seg), n - at,
                 layer)]

    fam.segments = segments
    return fam


@pytest.mark.parametrize("at", [1, 2])
def test_split_segments_give_the_same_logits(at):
    """AltUp's block index runs on across segments: the stack split at
    any layer computes what it computes whole."""
    params = _tiny_params()
    np.testing.assert_array_equal(_logits(params, _split_family(at)),
                                  _logits(params, DENSE))


def test_untied_head_equal_to_the_embedding_gives_the_tied_logits():
    params = _tiny_params()
    untied = {**params, "lm_head": params["embed"].T}
    np.testing.assert_allclose(_logits(untied, DENSE),
                               _logits(params, DENSE), rtol=0, atol=1e-6)


def test_reduced_key_reaches_the_program():
    """A configuration cut by `reduced` (the tiny preset's 3 layers to 2)
    builds the program's config through `cfg.replace`, passes the check
    against the file, and the weights have the program's layout."""
    import jax
    from repro.models.transformer import init_params
    config = copy.deepcopy(TINY)
    config["model"]["num_hidden_layers"] = 2
    config["reduced"] = ["num_hidden_layers"]
    cfg = driver.program_config(DENSE, config)
    assert cfg.n_layers == 2 and cfg.d_model == 64
    weights.check_layout(_tiny_params(config), jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    assert _logits(_tiny_params(config), DENSE, config=config).shape == \
        (40, 512)


def test_unreduced_change_and_width_cut_raise():
    """A number that differs from the program without being listed in
    `reduced` fails the check; a width may not be cut."""
    config = copy.deepcopy(TINY)
    config["model"]["num_hidden_layers"] = 2
    with pytest.raises(RuntimeError, match="differs"):
        driver.program_config(DENSE, config)
    config["model"]["hidden_size"] = 32
    config["reduced"] = ["hidden_size"]
    with pytest.raises(KeyError, match="hidden_size"):
        driver.program_config(DENSE, config)
