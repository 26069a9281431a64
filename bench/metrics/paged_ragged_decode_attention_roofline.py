"""Roofline share of the paged ragged decode-attention kernel
(`paged_ragged_decode_attention`): least time by the page-granular byte
model (whole pages per slot, block table and lengths) at each traced
decode step's slot depths, over the kernel's device time. Layer: decode
kernels."""
from benchcore.readers import attn_roofline


def read(run):
    return attn_roofline(run, "paged_ragged_decode_attention", paged=True)
