"""The one traffic generator. A mix is a data file under
``bench/traffic/<mix>.json``; this module reads it and makes the inputs
from ``--seed``.

Every seed gets the same multiset of sizes: lengths are fixed quantiles
of the mix's distribution, and the seed only permutes them and draws the
token ids or pixels. So two seeds do the same amount of work in another
order, and runs with different seeds spread no wider than runs of one.
"""
from __future__ import annotations

import json
import math
import pathlib
import statistics
from typing import Dict, List, Tuple

import numpy as np

from harness import seeds

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def load(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def quantile_lengths(dist: Dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``: a
    lognormal given by its median and sigma, or a uniform range; clipped
    to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            z = statistics.NormalDist().inv_cdf(q)
            v = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        elif dist["dist"] == "uniform":
            v = lo + (hi - lo) * q
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(min(max(int(round(v)), lo), hi))
    return out


def lm_round(mix: Dict, vocab: int, seed: int, index: int
             ) -> List[Tuple[str, np.ndarray, int]]:
    """Round ``index`` of a closed-loop batch job: ``mix["round"]``
    requests as (request id, prompt tokens, output length). Each round
    holds the same lengths; the seed and the round index permute them and
    draw the token ids."""
    n = int(mix["round"])
    prompts = quantile_lengths(mix["prompt"], n)
    outputs = quantile_lengths(mix["output"], n)
    g = seeds.rng(seed, 1, index)
    prompts = [prompts[i] for i in g.permutation(n)]
    outputs = [outputs[i] for i in g.permutation(n)]
    return [(f"r{index}.{i}",
             g.integers(0, vocab, size=(p,), dtype=np.int64).astype(np.int32),
             o) for i, (p, o) in enumerate(zip(prompts, outputs))]


def images(mix: Dict, image_size: int, seed: int):
    """The pool of ``mix["pool"]`` image batches a closed-loop client
    cycles through, made on the device in one call: (pool, batch, H, W,
    3) float32, standard normal per pixel and channel (normalized
    images)."""
    import jax
    import jax.numpy as jnp
    shape = (int(mix["pool"]), int(mix["batch"]), image_size, image_size, 3)
    make = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))
    return make(seeds.jax_key(seed, seeds.IMAGES))
