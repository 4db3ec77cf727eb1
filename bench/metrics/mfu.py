"""The whole step's share of the chip's int8 peak: the crossbar operations
of every real (unpadded) image the traced window sent (``2*M*K*N`` summed
over the stages of one image), over the window's length, over the peak.
Percent."""


def read(ctx):
    s = ctx.summary
    if s.window_s <= 0 or not ctx.sent:
        return None
    return (100.0 * ctx.work["real_ops"] / s.window_s
            / ctx.peak["int8_ops_per_s"])
