"""The one request generator; a traffic mix is a data file it reads.

A mix (``traffic/<name>.json``) gives:

* ``loop`` — ``"closed"``: one caller that keeps ``in_flight`` requests
  outstanding and sends the next as soon as the oldest has come back;
* ``sizes`` — images per request, as ``{size: weight}``;
* ``block`` — requests per block.  A block holds every size in
  proportion to its weight (largest remainders), the same multiset for
  every seed; the seed only orders it.  So seeds change the order of the
  work and not its amount;
* ``pool_images`` — images in the seeded host pool that requests are
  cut from (a request is ``size`` consecutive images of the pool, at an
  offset drawn from the seed);
* ``check_requests`` — finished requests the correctness check compares
  with the reference (the largest among them).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Iterator

import numpy as np

KEYS = ("loop", "in_flight", "sizes", "block", "pool_images",
        "check_requests", "why")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    in_flight: int
    sizes: dict[int, float]
    block: int
    pool_images: int
    check_requests: int

    @classmethod
    def load(cls, path: Path) -> "Mix":
        spec = json.loads(Path(path).read_text())
        unknown = set(spec) - set(KEYS)
        if unknown:
            raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
        if spec["loop"] != "closed":
            raise ValueError(f"{path}: only closed loops exist, not "
                             f"{spec['loop']!r}")
        sizes = {int(k): float(v) for k, v in spec["sizes"].items()}
        mix = cls(name=Path(path).stem, in_flight=int(spec["in_flight"]),
                  sizes=sizes, block=int(spec["block"]),
                  pool_images=int(spec["pool_images"]),
                  check_requests=int(spec["check_requests"]))
        if (mix.in_flight < 1 or min(sizes) < 1 or min(sizes.values()) <= 0
                or max(sizes) > mix.pool_images or mix.check_requests < 1):
            raise ValueError(f"{path}: sizes, weights, in_flight and "
                             "check_requests must be positive, sizes at "
                             "most pool_images")
        return mix

    def block_sizes(self) -> list[int]:
        """The sizes of one block, in ascending order (largest
        remainders, so the counts sum to ``block`` exactly)."""
        total = sum(self.sizes.values())
        want = {s: self.block * w / total for s, w in self.sizes.items()}
        counts = {s: int(v) for s, v in want.items()}
        short = self.block - sum(counts.values())
        for s in sorted(want, key=lambda s: (counts[s] - want[s], s))[:short]:
            counts[s] += 1
        return [s for s in sorted(counts) for _ in range(counts[s])]

    def distinct_sizes(self) -> list[int]:
        return sorted(set(self.block_sizes()))


def seed_words(seed: int) -> tuple[int, int]:
    """Any whole number -> two 32-bit words (JAX keys take 32 bits)."""
    seed %= 1 << 64
    return seed & 0xFFFFFFFF, seed >> 32


def requests(mix: Mix, seed: int) -> Iterator[tuple[int, int]]:
    """Endless (size, pool offset) stream: blocks shuffled by ``seed``."""
    rng = np.random.default_rng([*seed_words(seed), 1])
    block = np.array(mix.block_sizes())
    while True:
        for size in rng.permutation(block):
            size = int(size)
            yield size, int(rng.integers(0, mix.pool_images - size + 1))
