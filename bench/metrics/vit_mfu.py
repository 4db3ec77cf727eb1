"""The whole step's share of the chip's int8 peak in the vision
transformer's cell, defined as ``mfu``: the crossbar operations of every
real (unpadded) image the traced window sent (``2*M*K*N`` summed over
the reference's stages of one image, attention's per-(image, head)
stages counted once per head), over the window's length, over the peak.
Percent."""


def read(ctx):
    s = ctx.summary
    if s.window_s <= 0 or not ctx.sent:
        return None
    return (100.0 * ctx.work["real_ops"] / s.window_s
            / ctx.peak["int8_ops_per_s"])
