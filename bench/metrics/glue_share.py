"""Share of the device's op time in ops that are neither crossbar kernel:
im2col, quantization, mount layout, plane packing, batch padding and
slicing (``trace.classify``).  Percent of the summed op time."""


def read(ctx):
    total = sum(ctx.summary.class_s.values())
    if total <= 0:
        return None
    return 100.0 * ctx.summary.class_s["glue"] / total
