"""The one generator of traffic: reads a mix from ``traffic/<mix>.json``.

A serving mix is a closed loop of static batches.  Prompt lengths follow
a lognormal law, clipped and rounded up to a multiple of ``round_to``; one
cycle of ``cycle`` batches takes its lengths at the law's quantiles
(i + 1/2) / cycle, so every seed serves the same set of lengths.  The
cycle repeats in a fixed order that alternates short and long batches,
so a window of any length sees the law's mix.  Every request of a batch
has the batch's length: the engine left-pads a batch to its longest
prompt and attends to the padding, so a mixed batch would change each
request's answer (see PERF.md).  The seed draws the tokens, uniformly
from 2 .. vocab-1 (0 pads, 1 ends a sequence).

A training mix gives the batch, the sequence length and the optimizer;
the rows come from the program's own data pipeline, seeded with the run's
seed.
"""

from __future__ import annotations

import json
import math
import pathlib
from statistics import NormalDist
from typing import Iterator

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(mix: str) -> dict:
    path = HERE / "traffic" / f"{mix}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {mix!r}: {path} is missing")
    return json.loads(path.read_text())


def cycle_lengths(mix: dict) -> list[int]:
    """Prompt length of each batch of one cycle, in serving order."""
    p = mix["prompt"]
    n = mix["cycle"]
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        raw = min(max(p["median"] * math.exp(p["sigma"] * z), p["min"]),
                  p["max"])
        out.append(int(math.ceil(raw / p["round_to"]) * p["round_to"]))
    out.sort()
    order = []
    lo, hi = 0, n - 1
    while lo <= hi:
        order.append(out[lo])
        if hi != lo:
            order.append(out[hi])
        lo, hi = lo + 1, hi - 1
    return order


def serve_batches(mix: dict, vocab: int, seed: int
                  ) -> Iterator[list[list[int]]]:
    """Batches of prompts (lists of token ids), without end."""
    lengths = cycle_lengths(mix)
    k = 0
    while True:
        n = lengths[k % len(lengths)]
        rng = np.random.default_rng(np.random.SeedSequence([seed, k]))
        yield rng.integers(2, vocab, size=(mix["batch"], n)).tolist()
        k += 1
