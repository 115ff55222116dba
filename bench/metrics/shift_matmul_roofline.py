"""Kernel `kernels/shift_matmul.py`: roofline share of its device time in
the traced window. Its calls are the HLO instructions named after the
jitted `shift_matmul_pallas`."""
from bench.lib import cost
from bench.lib.context import kernel_roofline

PATTERN = r"^shift_matmul_pallas(\.\d+)?$"


def read(ctx):
    return kernel_roofline(ctx, PATTERN, cost.shift_matmul_calls,
                           cost.shift_matmul_cost)
