"""Model step: images completed per second in the part of the traced run's
window before the profiler started, times the arm's operations per image
(the family's `cost.ops_per_image`), over the chip's bf16 peak (float32 dots
run as bf16 passes at the default precision)."""


def read(ctx):
    if ctx.peaks is None or ctx.images_per_s <= 0:
        return None
    return (100.0 * ctx.images_per_s * ctx.family.cost.ops_per_image(ctx.cfg)
            / ctx.peaks["bf16_flops_per_s"])
