"""Kernel `kernels/shift_matmul.py`: roofline share of its device time in
the traced window. Its calls per forward are the family's
`cost.kernel_calls`; its device events are the HLO instructions named after
the jitted `shift_matmul_pallas`."""
from bench.lib import cost
from bench.lib.context import kernel_roofline

PATTERN = r"^shift_matmul_pallas(\.\d+)?$"


def read(ctx):
    def calls(cfg, batch):
        return ctx.family.cost.kernel_calls(cfg, batch)["shift_matmul"]
    return kernel_roofline(ctx, PATTERN, calls, cost.shift_matmul_cost)
