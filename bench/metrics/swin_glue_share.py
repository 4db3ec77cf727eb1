"""Share of the device's op time in ops that are neither crossbar kernel,
in Swin-T's cell, defined as ``glue_share`` and read by its reader: the
operands' build (window partitions and rolls, patch merging, the
pre-norms, quantization), the dynamic stages' mounts, the XLA tails
(layer norms, bias and mask, softmax, GELU, the token mean), batch
padding and slicing (``trace.classify``).  Percent of the summed op
time."""

from bench.metrics.glue_share import read  # noqa: F401
