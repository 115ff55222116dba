"""Model step: device time per forward of the MoE's routing and token
movement, the ops under the named scopes `moe_dispatch` and `moe_combine`
(inside `feed`)."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.scope_ms(ctx, ("moe_dispatch", "moe_combine"))
