"""Random weights from the seed, made on the device in one jitted call, in
the layout and types the served model takes them.

The benchmark makes the weights itself, so the plain reference
(`reference.py`) reads nothing the program made. The layout, every leaf
with its shape, type and kind in the order that seeds it, is the model
family's (`families/<family>.py` `layout`). `check_layout` compares it
with the program's own parameter shapes, so a change of layout stops the
run at set-up instead of feeding the program arrays it does not expect.

Values: every matmul weight ~ N(0, 1/fan_in); norm gains stored as
scale - 1 ~ N(0, 0.1^2) (the program applies x * (1 + g)); AltUp's
predictor p = I + N(0, 0.1^2) and corrector g = 1 + N(0, 0.1^2), so the
predict and correct steps are not the identity they are at init.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

VOCAB_PAD = 256


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def _leaf(key, shp, dtype, kind, vocab):
    if kind == "embed":
        x = jax.random.normal(key, shp) / math.sqrt(shp[-1])
        x = jnp.where(jnp.arange(shp[0])[:, None] < vocab, x, 0.0)
    elif kind == "norm":
        x = 0.1 * jax.random.normal(key, shp)
    elif kind == "altup_p":
        x = jnp.eye(shp[-1]) + 0.1 * jax.random.normal(key, shp)
    elif kind == "altup_g":
        x = 1.0 + 0.1 * jax.random.normal(key, shp)
    else:
        x = jax.random.normal(key, shp) / math.sqrt(kind)
    return x.astype(dtype)


def maker(leaves: Dict[str, tuple], vocab: int):
    """A jitted fn(seed_lo, seed_hi) -> params of the layout `leaves`
    ({path: (shape, dtype name, kind)}); one compile for all seeds. Leaf
    i draws from fold_in(base, i); embedding rows past `vocab` are 0."""

    def make(lo, hi):
        base = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0), lo), hi)
        flat = {path: _leaf(jax.random.fold_in(base, i), shp, dt, kind,
                            vocab)
                for i, (path, (shp, dt, kind)) in enumerate(leaves.items())}
        return _nest(flat)

    return jax.jit(make)


def make_params(leaves: Dict[str, tuple], vocab: int, seed: int):
    s = int(seed) % (1 << 64)
    return maker(leaves, vocab)(jnp.uint32(s & 0xFFFFFFFF),
                                jnp.uint32(s >> 32))


def check_layout(params, program_shapes) -> None:
    """Raise unless `params` has the program's tree, shapes and types."""
    ours = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), params)
    theirs = jax.tree_util.tree_map(lambda x: (tuple(x.shape), str(x.dtype)),
                                    program_shapes)
    if ours != theirs:
        raise RuntimeError(
            f"benchmark weight layout differs from the program's: "
            f"{ours} != {theirs}")
