"""``fb_epilogue``'s share of its roofline in Swin-T's cell, defined as
``epilogue_roofline`` and read by its reader: the bytes every epilogue
of every call must move, the windowed scores' per-mount epilogues
among them (``work.py``), at the HBM peak, over the kernel's summed
device time.  Percent."""

from bench.metrics.epilogue_roofline import read  # noqa: F401
