"""Test family: the dense decoder, with the reference's stack run as two
segments split after its first layer, so that AltUp's block index has to
run on across segments. It reuses the dense decoder's file from the same
`families/` directory, as a family of expert layers would reuse its
leading dense layer."""
from pathlib import Path

import jax

from benchcore import spec

_dense = spec.load_family("dense_decoder",
                          Path(__file__).resolve().parents[1])
program_config = _dense.program_config
layout = _dense.layout
arch = _dense.arch
shape = _dense.shape


def segments(params, arch):
    (seg, n, layer), = _dense.segments(params, arch)
    head = jax.tree_util.tree_map(lambda a: a[:1], seg)
    tail = jax.tree_util.tree_map(lambda a: a[1:], seg)
    return [(head, 1, layer), (tail, n - 1, layer)]
