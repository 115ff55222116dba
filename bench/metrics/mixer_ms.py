"""Model step: device time per forward of the ops under the named scope
`mixer` (norm1, attention with its q/k/v/o projections, residual add)."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.scope_ms(ctx, ("mixer",))
