"""The one traffic generator: a mix file's parameters become a request
stream.

A mix names its request kinds and their shares, either directly
(``"ops": {kind: share}``) or as TAO-style reads and writes
(``"read_frac"``, ``"reads": {kind: share}``, ``"writes": {kind:
share}``).  The stream is cut into blocks of ``"block"`` requests whose
composition is fixed by the shares (largest remainder), shuffled and
given vertices from the configuration's access distribution, so that
every seed sends the same number of each kind in another order.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator

import numpy as np


@dataclass
class Request:
    kind: str
    v: int          # vertex index (the access distribution's draw)
    u: int          # a second vertex (the destination of a create)


def shares(mix: dict) -> Dict[str, float]:
    if "ops" in mix:
        return dict(mix["ops"])
    rf = float(mix["read_frac"])
    out = {k: rf * s for k, s in mix["reads"].items()}
    for k, s in mix.get("writes", {}).items():
        out[k] = out.get(k, 0.0) + (1.0 - rf) * s
    return out


def composition(weights: Dict[str, float], block: int) -> Dict[str, int]:
    """Counts per kind in a block of ``block`` requests: floors of the
    exact shares, the rest to the largest remainders."""
    total = sum(weights.values())
    exact = {k: block * w / total for k, w in weights.items()}
    counts = {k: int(np.floor(x)) for k, x in exact.items()}
    rest = block - sum(counts.values())
    for k in sorted(exact, key=lambda k: (counts[k] - exact[k], k))[:rest]:
        counts[k] += 1
    return counts


def stream(weights: Dict[str, float], block: int, rng: np.random.Generator,
           draw) -> Iterator[Request]:
    """Endless requests; ``draw(rng, k)`` gives ``k`` vertex indices."""
    counts = composition(weights, block)
    kinds = np.array([k for k in sorted(counts) for _ in range(counts[k])])
    while True:
        order = rng.permutation(block)
        vs = draw(rng, block)
        us = draw(rng, block)
        for i in range(block):
            yield Request(str(kinds[order[i]]), int(vs[i]), int(us[i]))

