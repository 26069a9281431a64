"""Fused AltUp predict+correct Pallas TPU kernel.

Why a kernel: the predict (K x K block mix) and correct (rank-1 update)
steps are pure bandwidth — O(K^2 d) FLOPs against O(K d) bytes per token.
Left to XLA as separate einsums they make 2-3 HBM passes over the widened
(T, K, d) stream; the fused kernel streams each (bt, K, bd) tile through
VMEM exactly once: one read of x_wide, one read of x_tilde, one write of
x_new. The K x K scalar mix runs as VREG broadcasts (no MXU involvement),
so the kernel is memory-roofline optimal: bytes = 2*T*K*d + 2*T*d.

Tiling: bt x bd tiles with bd a multiple of 128 (lane width) and bt a
multiple of 8 (sublane) — the (K,) axis stays resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xw_ref, xt_ref, p_ref, g_ref, sel_ref, out_ref, *, K: int):
    xw = xw_ref[...].astype(jnp.float32)          # (bt, K, bd)
    xt = xt_ref[...].astype(jnp.float32)          # (bt, bd)
    p = p_ref[...].astype(jnp.float32)            # (K, K)
    g = g_ref[...].astype(jnp.float32)            # (K,)
    sel = sel_ref[...].astype(jnp.float32)        # (K,)
    # predict: xhat[i] = sum_j p[i, j] * xw[:, j]; K static & small ->
    # unrolled scalar-vector FMAs (VREG broadcasts, no MXU)
    blocks = [xw[:, j] for j in range(K)]
    xhat = [sum(p[i, j] * blocks[j] for j in range(K)) for i in range(K)]
    xhat_sel = sum(sel[k] * xhat[k] for k in range(K))
    delta = xt - xhat_sel
    out = jnp.stack([xhat[i] + g[i] * delta for i in range(K)], axis=1)
    out_ref[...] = out.astype(out_ref.dtype)


def altup_predict_correct(x_wide: jax.Array, x_tilde: jax.Array,
                          sel: jax.Array, p: jax.Array, g: jax.Array, *,
                          block_t: int = 256, block_d: int = 512,
                          interpret: bool | None = None) -> jax.Array:
    """x_wide: (T, K, d), x_tilde: (T, d) -> (T, K, d).

    interpret=None auto-detects from the backend (compiled on TPU,
    interpreted on CPU); pass a bool to force either mode.
    """
    if interpret is None:
        from repro.kernels import default_interpret
        interpret = default_interpret()
    T, K, d = x_wide.shape
    bt = min(block_t, T)
    bd = min(block_d, d)
    assert T % bt == 0 and d % bd == 0, (T, d, bt, bd)
    grid = (T // bt, d // bd)
    return pl.pallas_call(
        functools.partial(_kernel, K=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, K, bd), lambda i, j: (i, 0, j)),
            pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
            pl.BlockSpec((K, K), lambda i, j: (0, 0)),
            pl.BlockSpec((K,), lambda i, j: (0,)),
            pl.BlockSpec((K,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((bt, K, bd), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((T, K, d), x_wide.dtype),
        interpret=interpret,
        name="altup_predict_correct",
    )(x_wide, x_tilde, p, g, sel)
