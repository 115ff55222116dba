"""Engine: share of the traced window in which the device is idle while
one of the program's `engine.*` spans is open, i.e. the chip waits on the
engine's host work."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.idle_while_open(ctx, program_spans.ENGINE_SPANS)
