"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the largest, is run through the plain
reference, each request edge-padded to the mix's largest batch (the
copies of its last image leave every per-tensor scale as it was).  One
number is compared, with its limit from the configuration file
(``limits``):

* ``prob_err`` — the widest normwise gap over the sampled images:
  ``max_c |p_c - r_c| / max_c |r_c|`` per image, where ``p`` is what the
  program served and ``r`` the reference's probabilities.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import seed_words


def sample(done: list, n: int, seed: int) -> list:
    """``n`` finished requests: the largest (first of the largest), then
    others drawn from the seed."""
    if not done:
        return []
    largest = max(range(len(done)), key=lambda i: (done[i].size, -i))
    others = [i for i in range(len(done)) if i != largest]
    rng = np.random.default_rng([*seed_words(seed), 2])
    pick = rng.choice(len(others), size=min(n - 1, len(others)),
                      replace=False)
    return [done[largest]] + [done[others[i]] for i in sorted(pick)]


def well_formed(probs, size: int, classes: int) -> bool:
    return (probs is not None and probs.shape == (size, classes)
            and bool(np.isfinite(probs).all()))


def prob_err(got: np.ndarray, want: np.ndarray) -> float:
    """The widest per-image normwise gap (module docstring)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float((np.abs(got - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def compare(checked: list, reference, limits: dict) -> dict:
    """{name: (widest value over the sample, limit)}; ``reference(r)``
    gives the reference's probabilities of request ``r``'s batch."""
    worst = max((prob_err(r.probs, reference(r)[:r.size]) for r in checked),
                default=float("nan"))
    return {"prob_err": (worst, limits.get("prob_err"))}


def within(value: float, limit) -> bool:
    return limit is not None and value <= limit
