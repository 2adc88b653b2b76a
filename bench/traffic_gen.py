"""The one generator of traffic: request schedules and training batches
from the parameters of a traffic file and ``--seed``.

Every seed gets the same set of sizes and the same set of gaps between
arrivals, drawn once from the file's ``sizes_seed``; ``--seed`` only
chooses their order and the token ids.  So two seeds do the same work,
and a difference between them is the system's, not the traffic's.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    due: float               # seconds after the window opens
    prompt: np.ndarray       # (s,) int32
    max_new_tokens: int


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF for w in words])


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` (clipped to [min, max])."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def open_loop(traffic: dict, seed: int, seconds: float,
              vocab: int) -> list[Request]:
    """Poisson arrivals at ``traffic["rate_per_s"]``: ``round(rate *
    seconds)`` requests due inside the window, sorted by due time."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    fixed = _rng(traffic["sizes_seed"])
    prompts = draw_lengths(traffic["prompt"], n, fixed)
    outputs = draw_lengths(traffic["output"], n, fixed)
    gaps = fixed.exponential(1.0, n + 1)
    order = _rng(seed, 1)
    pairs = order.permutation(n)
    gaps = gaps[order.permutation(n + 1)]
    due = seconds * np.cumsum(gaps)[:n] / gaps.sum()
    ids = _rng(seed, 2)
    return [Request(float(due[i]),
                    ids.integers(0, vocab, int(prompts[pairs[i]]),
                                 dtype=np.int32),
                    int(outputs[pairs[i]]))
            for i in range(n)]


def zipf_tokens(shape, vocab: int, exponent: float,
                rng: np.random.Generator) -> np.ndarray:
    """Token ids whose frequencies fall off as rank^-exponent, the way
    words in text do; rank r is id r - 1."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(w / w.sum())
    u = rng.random(shape)
    return np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)


def train_batches(traffic: dict, seed: int, n: int, rows: int,
                  vocab: int) -> list[dict]:
    """``n`` batches of ``rows`` sequences of ``traffic["seq"]`` tokens
    with next-token labels; every row of every batch is drawn afresh."""
    seq = traffic["seq"]
    rng = _rng(seed, 3)
    out = []
    for _ in range(n):
        t = zipf_tokens((rows, seq + 1), vocab, traffic["zipf_exponent"], rng)
        out.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    return out
