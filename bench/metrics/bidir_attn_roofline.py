"""Kernel `kernels/bidir_linear_attention.py`: roofline share of its device
time in the traced window. Its calls are the HLO instructions named after
the jitted `bidir_binary_attention_pallas`."""
from bench.lib import cost
from bench.lib.context import kernel_roofline

PATTERN = r"^bidir_binary_attention_pallas(\.\d+)?$"


def read(ctx):
    return kernel_roofline(ctx, PATTERN, cost.bidir_attn_calls,
                           cost.bidir_attn_cost)
