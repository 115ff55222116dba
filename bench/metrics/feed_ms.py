"""Model step: device time per forward of the ops under the named scope
`feed` (norm2, MLP or MoE with its dispatch and experts, residual add)."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.scope_ms(ctx, ("feed",))
