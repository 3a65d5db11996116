"""The one traffic generator: every mix is a data file it reads.

A mix file (``perfbench/traffic/<name>.json``) holds only parameters. LM
mixes are closed loops of batch-generation jobs, run in whole cycles:

    {"kind": "lm_jobs", "batch": 32, "prompt_len": 161,
     "max_new_tokens": 1024,
     "gen": {"dist": "exponential", "mean": 337.99, "strata": 4},
     "check": {"jobs": 2, "rows": 4}}

Each job generates G tokens for ``batch`` rows. A cycle holds one job per
stratum: the ``strata`` equal-probability strata of the output-length
distribution, each job's G the mean of its stratum, so a cycle's mean is
the distribution's. Every seed runs the same G; the seed draws the order
of the jobs within each cycle and each job's prompt. Pricing mixes are
closed loops of whole workloads:

    {"kind": "pricing_workloads", "ci": 0.1,
     "check": {"tasks": 12, "ref_paths": 65536}}

each priced with a fresh stream seed drawn from the run's seed.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["gen_strata", "lm_cycle", "prompt_tokens", "stream_seed",
           "sample"]


def _exponential_stratum_mean(mean: float, p0: float, p1: float) -> float:
    """Mean of an exponential of mean ``mean`` between its quantiles
    ``p0`` < ``p1`` (``p1`` = 1 for the open top stratum)."""
    x0 = -mean * math.log1p(-p0)
    if p1 >= 1.0:
        return x0 + mean
    x1 = -mean * math.log1p(-p1)
    s0, s1 = 1.0 - p0, 1.0 - p1
    return mean + (x0 * s0 - x1 * s1) / (s0 - s1)


def gen_strata(gen: dict) -> list:
    """The G of each job in a cycle, shortest first: the stratum means of
    the output-length distribution, rounded, at least 1."""
    if gen.get("dist") != "exponential":
        raise ValueError(f"unknown output-length distribution {gen!r}")
    k, mean = int(gen["strata"]), float(gen["mean"])
    if k < 1 or mean <= 0:
        raise ValueError(f"bad output-length strata: {gen!r}")
    return [max(1, round(_exponential_stratum_mean(mean, i / k, (i + 1) / k)))
            for i in range(k)]


def stream_seed(seed: int, *path: int) -> int:
    """A 32-bit seed for one job or workload, from the run's seed."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), *map(int, path)])
    return int(ss.generate_state(1, np.uint32)[0])


def lm_cycle(traffic: dict, seed: int, c: int) -> list:
    """Cycle ``c`` of the loop: one job per stratum, in an order drawn
    from the seed, each a dict of ``gen`` (tokens to generate) and
    ``seed`` (the prompt's seed, handed to the system with the job)."""
    strata = gen_strata(traffic["gen"])
    k = len(strata)
    order = np.random.default_rng(stream_seed(seed, 3, c)).permutation(k)
    return [{"gen": strata[int(i)], "seed": stream_seed(seed, 1, c * k + n)}
            for n, i in enumerate(order)]


def prompt_tokens(vocab: int, batch: int, seq: int, seed: int) -> np.ndarray:
    """The prompt the system makes for a job's seed: Zipf(1.3) token ids
    from Philox keyed by the seed, clipped to the vocabulary (a copy of
    the system's synthetic-prompt rule, so the reference reads the same
    prompt without taking it from the system)."""
    rng = np.random.Generator(np.random.Philox(key=(int(seed) << 32) + 0))
    z = rng.zipf(1.3, size=(batch, seq))
    return np.minimum(z - 1, vocab - 1).astype(np.int32)


def sample(seed: int, n_items: int, k: int, salt: int) -> list:
    """``k`` distinct indices of ``n_items``, drawn from the seed."""
    rng = np.random.default_rng(stream_seed(seed, 2, salt))
    return sorted(int(i) for i in rng.choice(n_items, size=min(k, n_items),
                                             replace=False))
