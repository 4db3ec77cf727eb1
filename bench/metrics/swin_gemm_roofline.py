"""``mounted_gemm``'s share of its roofline in Swin-T's cell, defined as
``gemm_roofline`` and read by its reader: the roofline-bound time of
every GEMM stage of every call, each windowed Q.K^T and P.V stage
counted once per (image, window, head) mount (``work.py``), over the
kernel's summed device time.  Percent."""

from bench.metrics.gemm_roofline import read  # noqa: F401
