"""Crossbar program subsystem: compile a scheduled network, execute it.

``compile.py`` lowers a ``core/workload.py`` network through Algorithms
1 & 2 + sequence-pair decoding into a static ``CrossbarProgram`` (mount
rounds + FB ops with concrete tile shapes, weight slices, and buffer
wiring); ``pack.py`` mounts the weights at compile time (pre-quantized
int8 planes, conv layout, K padded to full mounts — the numeric
analogue of programming conductances); ``execute.py`` runs the packed
program batched under ``jax.jit``, activating all mounts of a stage in
one ``crossbar_gemm`` K-grid dispatch and every post-op chain in one
fused ``fb_epilogue`` pass.  ``repro.api`` builds the user-facing
surface on top of this subsystem (builder graphs, unified
``HurryConfig``, persistable ``CompiledModel`` sessions whose ``run``
is the serving entry); nothing here imports it.
"""

from .compile import (CrossbarProgram, MountRound, ProgramOp,
                      compile_network)
from .execute import execute_packed
from .pack import PackedProgram, PackedStage, pack_program

__all__ = [
    "CrossbarProgram", "MountRound", "ProgramOp", "compile_network",
    "PackedProgram", "PackedStage", "pack_program",
    "execute_packed",
]
