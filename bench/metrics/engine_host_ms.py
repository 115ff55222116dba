"""Engine: host time per batch of `BucketedViTEngine.infer` (the program's
spans `engine.put`, `engine.pad`, `engine.enqueue` and `engine.slice`),
mean over the batches in the traced window."""
from bench.lib import program_spans

program_spans.start()


def read(ctx):
    return program_spans.per_batch_ms(ctx, program_spans.ENGINE_SPANS)
