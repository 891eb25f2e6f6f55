"""The token stream of a traffic mix, made from the seed on the host.

Ids follow a Zipf law of exponent ``zipf_a`` over ranks ``1..vocab``,
truncated to the vocabulary slice the configuration holds: a draw past the
slice is drawn again, never clipped, so no id piles up at ``vocab - 1``.
Step ``k`` of seed ``s`` always gives the same (global batch, seq + 1)
block, whatever ran before it, so the program, the reference and a second
run of one seed all see the same rows.
"""
from __future__ import annotations

import numpy as np


def truncated_zipf(rng: np.random.Generator, a: float, vocab: int,
                   size: int) -> np.ndarray:
    """``size`` ids in ``[0, vocab)``; id ``r - 1`` has probability
    proportional to ``r ** -a``."""
    ids = rng.zipf(a, size=size) - 1
    bad = np.flatnonzero(ids >= vocab)
    while bad.size:
        ids[bad] = rng.zipf(a, size=bad.size) - 1
        bad = bad[ids[bad] >= vocab]
    return ids.astype(np.int32)


def step_tokens(traffic: dict, vocab: int, seed: int, step: int
                ) -> np.ndarray:
    """Step ``step``'s global batch: (workers · seqs_per_worker, seq + 1)
    int32 token ids. Rows are laid out worker by worker, as
    ``worker_split`` reads them."""
    rows = int(traffic["workers"]) * int(traffic["seqs_per_worker"])
    width = int(traffic["seq"]) + 1
    rng = np.random.default_rng([int(seed), int(step)])
    return truncated_zipf(rng, float(traffic["zipf_a"]), vocab,
                          rows * width).reshape(rows, width)
