"""``mounted_gemm``'s share of its roofline: the summed roofline-bound time
of every GEMM stage of every call in the traced window (``work.py``: int8
operations at the int8 peak or bytes at the HBM peak, whichever is
longer, at the real shapes) over the kernel's summed device time.
Percent."""


def read(ctx):
    t = ctx.summary.class_s["gemm"]
    if t <= 0:
        return None
    return 100.0 * ctx.work["gemm_bound_s"] / t
