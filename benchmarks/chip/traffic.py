"""The one traffic generator.  A mix is a data file, ``traffic/<mix>.json``:

``loop``
    ``"backlog"``: offline batch.  The window starts with ``slots +
    backlog`` requests queued and tops the queue up so that at least
    ``backlog`` requests wait unstarted at every step.
    ``"poisson"``: open loop at ``rate_per_s`` requests per second.
``prompt_tokens``, ``output_tokens``
    ``{"dist": "log_uniform" | "uniform", "min": a, "max": b}``.
``block``
    Lengths are drawn as the ``block`` quantiles of each distribution,
    and every block of ``block`` requests holds that same set in an order
    drawn from the seed (prompts and outputs permuted apart).  Poisson
    gaps are likewise the quantiles of the exponential over the window,
    in an order drawn from the seed.  So every seed offers the same sizes
    and the same arrivals, and the seed changes only their order and the
    token ids.

Decoding is greedy with no end-of-sequence token: every request emits
exactly its output length.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Iterator, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Planned:
    """One request as the traffic offers it."""

    index: int
    prompt: np.ndarray      # int32 token ids
    max_new: int            # output tokens, the prefill's first token included
    due: Optional[float]    # seconds after the window opens (open loop), else None


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(spec: dict, k: int) -> np.ndarray:
    lo, hi = float(spec["min"]), float(spec["max"])
    u = (np.arange(k) + 0.5) / k
    if spec["dist"] == "log_uniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.rint(v).astype(np.int64)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator for one use of the seed."""
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(stream,)))


def prompt_buckets(mix: dict, max_len: int) -> List[int]:
    """The power-of-two prefill buckets the mix's prompts fall in."""
    lo, hi = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
    out = set()
    for n in range(lo, hi + 1):
        out.add(max(n, min(1 << (n - 1).bit_length(), max_len)))
    return sorted(out)


def requests(mix: dict, seed: int, vocab: int, seconds: float) -> Iterator[Planned]:
    """The mix's requests in order, without end."""
    rng = rng_for(seed, 1)
    k = int(mix["block"])
    plens = _quantiles(mix["prompt_tokens"], k)
    outs = _quantiles(mix["output_tokens"], k)
    gaps = None
    if mix["loop"] == "poisson":
        rate = float(mix["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    elif mix["loop"] != "backlog":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    due, i = 0.0, 0
    order_gaps = []
    while True:
        pl, ol = rng.permutation(plens), rng.permutation(outs)
        for j in range(k):
            if gaps is not None:
                if not order_gaps:
                    order_gaps = list(rng.permutation(gaps))
                due += float(order_gaps.pop())
            prompt = rng.integers(0, vocab, int(pl[j]), dtype=np.int32)
            yield Planned(i, prompt, int(ol[j]), due if gaps is not None else None)
            i += 1
