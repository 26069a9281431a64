"""Roofline share of the contiguous-cache ragged decode-attention kernel
(`ragged_decode_attention`): least time by the byte model at each traced
decode step's slot depths (each slot's rows of K and V, query in, output
out), over the kernel's device time. Layer: decode kernels."""
from benchcore.readers import attn_roofline


def read(run):
    return attn_roofline(run, "ragged_decode_attention", paged=False)
