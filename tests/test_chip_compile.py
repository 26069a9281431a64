"""Compile the serving path's Pallas kernels for a TPU v5e chip that is
described, not attached.

Interpret-mode tests cannot see what the chip's compiler refuses (block
shapes off the (8, 128) tiling, too much VMEM, ...). These tests lower
and compile at Qwen3-0.6B widths (B=8 slots, T=4096, Hk=8 kv heads,
rep=2, dh=128; AltUp K=2, d=1024) against a described `v5e:2x2`
topology and check that each kernel lands as a `tpu_custom_call`.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import altup_fused, compiled_kernels
from repro.kernels import ragged_decode_attention as R

B, T, HK, REP, DH = 8, 4096, 8, 2, 128
PAGE = 16


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under the system temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_ragged_decode_attention_compiles(one_chip, dtype):
    s = functools.partial(_spec, one_chip)
    args = [s((B, HK, REP, DH), jnp.bfloat16), s((B, T, HK, DH), dtype),
            s((B, T, HK, DH), dtype), s((B,), jnp.int32)]
    if dtype == jnp.int8:
        args += [s((B, T, HK), jnp.float32)] * 2

    def fn(q, k, v, lengths, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return R.ragged_decode_attention(q, k, v, lengths, interpret=False,
                                         **kw)

    assert compiled_kernels(_compile(fn, *args)) == {
        "ragged_decode_attention"}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_paged_ragged_decode_attention_compiles(one_chip, dtype):
    s = functools.partial(_spec, one_chip)
    rows = B * T                       # a pool of B full-length slots
    args = [s((B, HK, REP, DH), jnp.bfloat16), s((rows, HK, DH), dtype),
            s((rows, HK, DH), dtype), s((B,), jnp.int32),
            s((B, T // PAGE), jnp.int32)]
    if dtype == jnp.int8:
        args += [s((rows, HK), jnp.float32)] * 2

    def fn(q, k, v, lengths, table, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return R.paged_ragged_decode_attention(
            q, k, v, lengths, table, page=PAGE, t_max=T, interpret=False,
            **kw)

    assert compiled_kernels(_compile(fn, *args)) == {
        "paged_ragged_decode_attention"}


def test_fused_altup_decode_compiles(one_chip):
    s = functools.partial(_spec, one_chip)
    K, d = 2, 1024
    # the decode wrapper flattens (B, S=1) to B tokens, blocks of B rows
    hlo = _compile(
        functools.partial(altup_fused.altup_predict_correct, block_t=B,
                          interpret=False),
        s((B, K, d), jnp.bfloat16), s((B, d), jnp.bfloat16),
        s((K,), jnp.float32), s((K, K), jnp.float32), s((K,), jnp.float32))
    assert compiled_kernels(hlo) == {"altup_predict_correct"}


@pytest.mark.parametrize("layout", ["contiguous", "int8", "paged"])
def test_decode_step_compiles_with_kernels(one_chip, layout):
    """One whole 28-layer decode step with both decode kernels on, over
    each cache layout the engine serves: the kernels are chosen when the
    step is traced, so the step must hold them as custom calls, not
    their interpreted bodies."""
    import repro.kernels
    # imported before the patch below: a mode chosen at import would be
    # the CPU's and leave the step without custom calls
    import repro.kernels.ops  # noqa: F401
    from repro.configs import get_config
    from repro.models.decode import (decode_step, init_cache,
                                     init_paged_cache)
    from repro.models.transformer import init_params

    cfg = get_config("qwen3-0.6b", altup_k=2).replace(
        ragged_decode_attn=True, fused_decode_altup=True,
        kv_cache_dtype="int8" if layout == "int8" else "auto")
    place = lambda t: jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    kw = {}
    if layout == "paged":
        caches = place(jax.eval_shape(lambda: init_paged_cache(
            cfg, B, T, n_pages=B * T // PAGE, page=PAGE)))
        kw = {"block_table": _spec(one_chip, (B, T // PAGE), jnp.int32)}
    else:
        caches = place(jax.eval_shape(lambda: init_cache(cfg, B, T)))
    tokens = _spec(one_chip, (B, 1), jnp.int32)
    pos = _spec(one_chip, (B,), jnp.int32)
    step = functools.partial(decode_step, cfg=cfg, kv_len=T,
                             page_size=PAGE if layout == "paged" else 0)
    with pytest.MonkeyPatch.context() as mp:
        # this process runs on the CPU; steer the kernels to the chip's
        # compiled mode for this trace only (and trace afresh)
        mp.setattr(repro.kernels, "default_interpret", lambda: False)
        jax.clear_caches()
        try:
            hlo = jax.jit(step).lower(params, caches=caches, tokens=tokens,
                                      pos=pos, **kw).compile().as_text()
        finally:
            jax.clear_caches()
    attn = ("paged_ragged_decode_attention" if layout == "paged"
            else "ragged_decode_attention")
    assert compiled_kernels(hlo) == {attn, "altup_predict_correct"}
