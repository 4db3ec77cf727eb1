"""Operations and bytes of the crossbar stages, from their shapes.

The shapes come from the plain reference (``reference.Ref`` records each
GEMM stage), so they follow the crossbar semantics and not the layout or
the dtype that a program version uses: K is the real contraction length,
never padded to a mount or a tile.

* GEMM: ``2*M*K*N`` int8 operations; bytes ``M*K + K*N`` (int8 operands)
  plus ``4*M*N`` (the int32 result).
* Epilogue: bytes ``4*M*N`` (int32 in), ``4*M*N`` more where a residual
  is added, and ``4*out_rows*N`` (float32 out).  Its arithmetic is a few
  operations per element, so the bound is the bytes.
"""

from __future__ import annotations

import functools

import jax

from bench.reference import EXACT, GemmShape, Ref


def stage_shapes(family, sizes: dict, batch: int) -> list[GemmShape]:
    """Every GEMM stage of one forward at ``batch``, in order."""
    shapes: list[GemmShape] = []
    ref = Ref(EXACT, record=shapes.append)
    params = jax.eval_shape(functools.partial(family.init, sizes=sizes),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, sizes["input_hw"], sizes["input_hw"],
                              sizes["input_ch"]), "float32")
    jax.eval_shape(lambda p, v: family.reference(p, v, sizes, ref), params, x)
    return shapes


def gemm_ops(s: GemmShape) -> int:
    return 2 * s.m * s.k * s.n * s.count


def gemm_bytes(s: GemmShape) -> int:
    return (s.m * s.k + s.k * s.n + 4 * s.m * s.n) * s.count


def epilogue_bytes(s: GemmShape) -> int:
    rows = s.out_rows or s.m
    per = 4 * s.m * s.n * (2 if s.residual else 1) + 4 * rows * s.n
    return per * s.count


def bound_s(ops: float, nbytes: float, ops_per_s: float,
            bytes_per_s: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / ops_per_s, nbytes / bytes_per_s)


def totals(shapes: list[GemmShape], peak: dict) -> dict:
    """Summed work of one forward and its roofline-bound times."""
    ops = sum(gemm_ops(s) for s in shapes)
    return {
        "ops": ops,
        "gemm_bound_s": sum(bound_s(gemm_ops(s), gemm_bytes(s),
                                    peak["int8_ops_per_s"],
                                    peak["hbm_bytes_per_s"])
                            for s in shapes),
        "epilogue_bound_s": sum(epilogue_bytes(s) for s in shapes)
        / peak["hbm_bytes_per_s"],
    }
