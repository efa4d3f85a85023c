"""The whole step's share of the chip's bf16 peak: the configuration's
``model_flops_per_image`` (from the plain reference, never from the program)
times the images of the traced steps, over the device-timeline span of those
steps, over the peak of every chip used."""


def read(ctx, reading):
    s = reading["summary"]
    flops = (ctx.config["model_flops_per_image"] * reading["images_per_step"]
             * s["steps"])
    peak = ctx.peaks["bf16_flops_per_s"] * reading["chips"]
    return 100.0 * flops / s["span_s"] / peak
