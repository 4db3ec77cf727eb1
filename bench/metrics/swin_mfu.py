"""The whole step's share of the chip's int8 peak in Swin-T's cell,
defined as ``mfu`` and read by its reader: the crossbar operations of
every real image the traced window sent (``2*M*K*N`` summed over the
reference's stages of one image, attention's per-(image, window, head)
stages counted once per mount), over the window's length, over the
peak.  Percent."""

from bench.metrics.mfu import read  # noqa: F401
