"""``fb_epilogue``'s share of its roofline: the bytes every epilogue of
every call in the traced window must move (int32 in, residual, float32
out at the real shapes; ``work.py``) at the HBM peak, over the kernel's
summed device time.  Percent."""


def read(ctx):
    t = ctx.summary.class_s["epilogue"]
    if t <= 0:
        return None
    return 100.0 * ctx.work["epilogue_bound_s"] / t
