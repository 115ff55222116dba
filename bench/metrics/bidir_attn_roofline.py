"""Kernel `kernels/bidir_linear_attention.py`: roofline share of its device
time in the traced window. Its calls per forward are the family's
`cost.kernel_calls`; its device events are the HLO instructions named after
the jitted `bidir_binary_attention_pallas`."""
from bench.lib import cost
from bench.lib.context import kernel_roofline

PATTERN = r"^bidir_binary_attention_pallas(\.\d+)?$"


def read(ctx):
    def calls(cfg, batch):
        return ctx.family.cost.kernel_calls(cfg, batch)["bidir_attn"]
    return kernel_roofline(ctx, PATTERN, calls, cost.bidir_attn_cost)
