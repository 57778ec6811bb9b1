"""One generator for every traffic mix: a mix is a JSON file of parameters.

Lengths are drawn by stratified sampling, and are the same for every seed.
A block is one fill of the cell's slots: ``block`` requests, the
distribution's quantiles at (i + 1/2) / block, each prompt quantile paired
with an output quantile. The block is served in groups: its pairs, sorted
by prompt, fall into ``group`` strata of equal size, and each run of
``group`` consecutive requests takes one pair from every stratum. The
pairing and the order are drawn once, from a fixed stream, and every block
repeats them. So the slots start on the same work in every run, each later
group of admissions brings prefills of about the same lengths, and the
window, which sees only the first fills of the backlog, holds the same
work whatever the seed. The seed draws the prompts' token ids (and the
harness draws the weights from it).

Keys of a mix file:

- ``prompt``: ``{"median", "sigma"}`` of a lognormal, and ``snap_up``: the
  sorted lengths a drawn length is rounded up to (above the last, the last);
- ``output``: ``{"median", "sigma", "min", "max"}``, a lognormal clipped to
  [min, max] and rounded;
- ``group``: strata per block, a divisor of every cell's slot count;
- ``arrival``: ``"backlog"``, every request present at step 0.
"""

from __future__ import annotations

import bisect
import math
from statistics import NormalDist

import numpy as np


def _quantiles(block: int) -> list[float]:
    nd = NormalDist()
    return [nd.inv_cdf((i + 0.5) / block) for i in range(block)]


def block_lengths(spec: dict, block: int) -> list[tuple[int, int]]:
    """The (prompt_len, max_new_tokens) pairs of one block, in serving
    order."""
    z = _quantiles(block)
    p, o = spec["prompt"], spec["output"]
    k = spec["group"]
    if block % k:
        raise ValueError(f"{block} slots do not split into groups of {k}")
    snap = sorted(p["snap_up"])
    prompts = []
    for zi in z:
        x = p["median"] * math.exp(p["sigma"] * zi)
        prompts.append(snap[min(bisect.bisect_left(snap, x), len(snap) - 1)])
    outputs = [int(min(max(round(o["median"] * math.exp(o["sigma"] * zi)),
                           o["min"]), o["max"])) for zi in z]
    rng = np.random.default_rng(0)
    pairs = sorted(zip(prompts, (outputs[j] for j in rng.permutation(block))))
    size = block // k
    strata = [[pairs[s * size + i] for i in rng.permutation(size)]
              for s in range(k)]
    return [strata[s][g] for g in range(size) for s in rng.permutation(k)]


def mean_output(spec: dict, block: int) -> float:
    return float(np.mean([o for _, o in block_lengths(spec, block)]))


def lengths(spec: dict, block: int, n_blocks: int) -> list[tuple[int, int]]:
    """(prompt_len, max_new_tokens) of ``n_blocks`` blocks of ``block``
    requests, in serving order."""
    if spec.get("arrival") != "backlog":
        raise ValueError(f"unsupported arrival {spec.get('arrival')!r}")
    return block_lengths(spec, block) * n_blocks


def prompts(lens: list[tuple[int, int]], vocab_size: int, seed: int
            ) -> list[np.ndarray]:
    """Uniform token ids for each prompt, from a stream apart from the
    lengths' so that a mix's lengths do not depend on the vocabulary."""
    rng = np.random.default_rng([seed, 1])
    flat = rng.integers(0, vocab_size, size=sum(p for p, _ in lens),
                        dtype=np.int32)
    return np.split(flat, np.cumsum([p for p, _ in lens])[:-1])
