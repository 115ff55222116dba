"""Replicas: time a submitted batch waits for a replica thread (the
program's span `replica.queued`), mean over the batches in the traced
window."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.per_batch_ms(ctx, ("replica.queued",))
