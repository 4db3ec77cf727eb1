"""Share of the device's op time in ops that are neither crossbar kernel,
in the vision transformer's cell, defined as ``glue_share``: the
operands' build (token reshapes, the pre-norms, quantization, the
patchify im2col), the dynamic stages' mounts, the embedding, batch
padding and slicing (``trace.classify``).  Percent of the summed op
time."""


def read(ctx):
    total = sum(ctx.summary.class_s.values())
    if total <= 0:
        return None
    return 100.0 * ctx.summary.class_s["glue"] / total
