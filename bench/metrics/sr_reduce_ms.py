"""Model step: device time per forward of the ops under the named scope
`sr_reduce` (PVT's spatial reduction of the attention's k/v tokens: the
sr x sr strided convolution and its LayerNorm, inside `mixer`)."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.scope_ms(ctx, ("sr_reduce",))
