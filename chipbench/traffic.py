"""The one traffic generator: turns a mix file and a seed into inputs.

A mix is a JSON file under ``chipbench/traffic/`` whose ``kind`` picks one
of the shapes below; every other number in it is a parameter. The program
only ever sees what this module generates.

* ``token_rows``: pretraining rows of ``seq_len + 1`` token ids drawn
  uniformly from the vocabulary, a new row for every row of every step
  (``input_ids`` = row[:-1], ``labels`` = row[1:]).
* ``open_loop``: independent users. ``rate_per_s * seconds`` requests per
  window. Prompt and output lengths are the stratified quantiles of
  clipped log-normals, and the gaps between arrivals the stratified
  quantiles of an exponential (Poisson arrivals) normalised to fill the
  window, in one fixed shuffled order: with a dozen or so requests in a
  window, which long request comes first moves every metric, so every seed
  gets the same lengths at the same times and draws only the token ids
  (and the weights). Every ``sample_every``-th request samples at
  ``temperature``; the others are greedy.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List

import numpy as np


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """A numpy generator keyed by the whole seed (any size) and ``tags``."""
    return np.random.default_rng([abs(int(seed)), *tags])


# ------------------------------------------------------------- training


def token_rows(mix, seed: int, rows: int, vocab: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless batches of ``rows`` rows; batch i depends only on (seed, i)."""
    seq = mix["seq_len"]
    i = 0
    while True:
        r = rng_for(seed, 1, i).integers(0, vocab, size=(rows, seq + 1),
                                         dtype=np.int32)
        yield {"input_ids": r[:, :-1], "labels": r[:, 1:]}
        i += 1


# -------------------------------------------------------------- serving


def _lognormal_quantiles(spec, n: int) -> np.ndarray:
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = spec["median"] * np.exp(spec["sigma"] * np.asarray(z))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def open_loop(mix, seed: int, seconds: float, vocab: int) -> List[Dict[str, Any]]:
    """Requests due in a window of ``seconds``: dicts with ``due`` (seconds
    from the window's start), ``prompt`` (int32 ids), ``max_new_tokens``,
    ``temperature`` and ``top_k``."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = rng_for(0, 2)  # the order is the same for every seed
    prompts = rng.permutation(_lognormal_quantiles(mix["prompt"], n))
    outputs = rng.permutation(_lognormal_quantiles(mix["output"], n))
    # n + 1 exponential gaps (the last one runs past the final arrival to
    # the window's end), normalised to the window: Poisson arrivals given
    # n of them in the window.
    gaps = np.asarray([-math.log(1.0 - (i + 0.5) / (n + 1))
                       for i in range(n + 1)])
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps / gaps.sum() * seconds)[:n]
    every = mix.get("sample_every", 0)
    out = []
    for i in range(n):
        sampled = bool(every) and i % every == every - 1
        ids = rng_for(seed, 3, i).integers(1, vocab, size=int(prompts[i]),
                                           dtype=np.int32)
        out.append({
            "due": float(due[i]), "prompt": ids,
            "max_new_tokens": int(outputs[i]),
            "temperature": float(mix["temperature"]) if sampled else 0.0,
            "top_k": int(mix.get("top_k", 0)) if sampled else 0,
        })
    return out
