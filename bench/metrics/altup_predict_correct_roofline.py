"""Roofline share of the fused AltUp predict+correct kernel
(`altup_predict_correct`), which runs in every layer of every fused step:
least time by its operand bytes (the widened stream in and out, the layer
output in) over the kernel's device time. Layer: decode kernels."""
from benchcore import models
from benchcore.readers import kernel_roofline


def read(run):
    def cost(c, _layer):
        return models.altup_kernel_cost(run.shape,
                                        len(c["pos"]) * c["width"])

    return kernel_roofline(run, "altup_predict_correct", cost,
                           lambda c: True)
