"""``mounted_gemm``'s share of its roofline in the vision transformer's
cell, defined as ``gemm_roofline``: the summed roofline-bound time of
every GEMM stage of every call in the traced window — the static stages
and attention's dynamic Q.K^T and P.V stages, ``count`` = batch x heads
products each (``work.py``, from the reference's shapes) — over the
kernel's summed device time.  Percent."""


def read(ctx):
    t = ctx.summary.class_s["gemm"]
    if t <= 0:
        return None
    return 100.0 * ctx.work["gemm_bound_s"] / t
