"""Model step: device time per forward of the ops under the named scope
`ffn_dwconv` (PVT's Mix-FFN depthwise 3x3 convolution and GELU between the
two linears, inside `feed`)."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.scope_ms(ctx, ("ffn_dwconv",))
