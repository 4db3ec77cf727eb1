"""Default tile sizes of the crossbar kernels — the one place they are chosen.

``HurryConfig.block_m/block_n`` and the executor forward an explicit
override when one is given; otherwise each kernel takes its path's
entry here.  The sizes are what lets each path compile for the TPU
(v5e, default scoped VMEM), not a tuning result:

* ``exact`` — ``crossbar_gemm``'s clip-free int8 GEMM: 512x512 int8
  tiles plus their f32 casts and the int32 accumulator fit.
* ``sliced`` — the plane-packed ADC path materializes 8x plane-stacked
  f32 operands and an ``(8*bm, 8*bn)`` counts block per tile; 256x256
  already exceeds VMEM at 512-row mounts, so it takes 128x128.
* ``epilogue`` — ``fb_epilogue``'s f32 row tiles (pooled and seq-mean
  modes size their row blocks from whole images instead).
"""

from __future__ import annotations

_BLOCKS = {"exact": (512, 512), "sliced": (128, 128), "epilogue": (256, 128)}


def default_blocks(path: str) -> tuple[int, int]:
    """(block_m, block_n) for ``path`` in {"exact", "sliced", "epilogue"}."""
    return _BLOCKS[path]
