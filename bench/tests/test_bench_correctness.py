"""The check that decides ``correct``, driven through the rest of a run
on the CPU at a tiny size, the look for a chip skipped.

The program passes.  Each control (the plain reference computed at a
lower precision, in the program's place) and each fault the served path
can have (an answer altered where it is produced, half of a batch left
out) comes out as not correct under the configurations' own limits.
A step that returns its state unchanged and the exchange between chips
do not exist in a one-chip inference cell.
"""

import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference import CONTROLS
from bench.tests.tiny import TINY, make_tree

SEED = 2**33 + 17


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


def _run(tree, family, **kw):
    cell = harness.find_cell(tree, f"{family}.tiny-mix", tree / "bench")
    return harness.run(cell, SEED, 0.3, False, t_process=time.perf_counter(),
                       require_tpu=False, cache=False, **kw)


def alter_one_answer(call):
    def f(x):
        y = call(x)
        return y.at[0].set(jnp.roll(y[0], 1))
    return f


def drop_half_the_batch(call):
    def f(x):
        y = call(x)
        keep = x.shape[0] - x.shape[0] // 2
        return y.at[keep:].set(y[:keep].mean(axis=0))
    return f


@pytest.mark.parametrize("family", sorted(TINY))
def test_program_is_correct(tree, family):
    r = _run(tree, family)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("family", sorted(TINY))
def test_control_is_not_correct(tree, family, control):
    assert not _run(tree, family, control=control)["correct"]


@pytest.mark.parametrize("fault", [alter_one_answer, drop_half_the_batch],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("family", sorted(TINY))
def test_fault_is_not_correct(tree, family, fault):
    assert not _run(tree, family, fault=fault)["correct"]
