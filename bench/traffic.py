"""The one traffic generator: turns a mix's parameters and a seed into the
work of a run.

A mix is a JSON file under ``bench/traffic``. Every draw comes from the
seed, through ``numpy``'s ``SeedSequence``, so the same seed gives the same
work; seeds may be any non-negative integer, 64 bits and beyond.

Open-loop schedules are stratified: the number of requests is the rate
times the window, the lengths are the stated probabilities times that
number (largest remainder), and the gaps between arrivals are the
exponential distribution's quantiles at the midpoints of equal-probability
strata, normalized to the rate. The window is cut into blocks of about
``BLOCK`` requests and each of the three lists is dealt over the blocks in
sorted order, so that every block offers the mix's proportions and a share
of short and long gaps; the seed shuffles each block. Every seed thus
offers the same work at the same mean rate with Poisson-shaped gaps, in
another order, and no seed piles the long requests into one stretch of the
window: seeds differ by arrangement and not by amount.
"""

from __future__ import annotations

import numpy as np

BLOCK = 10  # requests per block of an open-loop schedule


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of draws of a run."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            break
    return np.random.default_rng(np.random.SeedSequence(words + [0xBE7C, *stream]))


def exact_counts(probs, n: int) -> np.ndarray:
    """Integer counts summing to ``n`` closest to ``probs * n`` (largest
    remainder)."""
    probs = np.asarray(probs, float)
    if probs.min() < 0 or not np.isclose(probs.sum(), 1.0):
        raise ValueError(f"probabilities must be >= 0 and sum to 1: {probs}")
    raw = probs * n
    counts = np.floor(raw).astype(int)
    short = n - counts.sum()
    counts[np.argsort(counts - raw, kind="stable")[:short]] += 1
    return counts


def exponential_gaps(n: int, mean: float) -> np.ndarray:
    """``n`` gaps with the exponential distribution's shape (its quantiles
    at the midpoints of ``n`` equal-probability strata) and mean exactly
    ``mean``, in ascending order."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * (mean / gaps.mean())


def dealt(values, blocks: int, gen: np.random.Generator) -> np.ndarray:
    """``values`` dealt over ``blocks`` blocks in sorted order, snaking so
    that no block gets the larger card of every round, each block then
    shuffled; the blocks concatenated in order."""
    ranked = np.sort(np.asarray(values))
    out = [[] for _ in range(blocks)]
    for i, v in enumerate(ranked):
        rnd, pos = divmod(i, blocks)
        out[pos if rnd % 2 == 0 else blocks - 1 - pos].append(v)
    for block in out:
        gen.shuffle(block)
    return np.concatenate([np.asarray(b, ranked.dtype) for b in out])


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests due in a window of ``seconds``: dicts with ``due``
    (seconds from the window's start), ``prompt`` (int32 token ids, uniform
    over the vocabulary) and ``new_tokens``. The gaps add up to the window,
    so every request is due inside it and the rate is the mix's up to
    rounding the count."""
    n = int(round(mix["rate_per_s"] * seconds))
    if n < 1:
        raise ValueError(f"{mix['rate_per_s']}/s over {seconds} s is no request")
    blocks = max(1, round(n / BLOCK))

    def lengths(key, stream):
        d = mix[key]
        return dealt(np.repeat(d["values"], exact_counts(d["probs"], n)), blocks,
                     rng(seed, stream))

    prompts, news = lengths("prompt_tokens", 1), lengths("new_tokens", 2)
    gaps = dealt(exponential_gaps(n, seconds / n), blocks, rng(seed, 3))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    tok_gen = rng(seed, 4)
    return [
        {
            "due": float(due[i]),
            "prompt": tok_gen.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            "new_tokens": int(news[i]),
        }
        for i in range(n)
    ]


def shapes(mix: dict) -> list:
    """Every (prompt length, cache capacity) a served mix uses: the cache
    holds the prompt and the mix's longest answer."""
    cap = max(mix["new_tokens"]["values"])
    return [(int(t), int(t) + cap) for t in sorted(mix["prompt_tokens"]["values"])]


def sweep_seeds(seed: int, call: int, k: int) -> tuple:
    """The simulator seeds of the ``call``-th sweep of a run: ``k`` integers
    below 2**63."""
    return tuple(int(s) for s in rng(seed, 5, call).integers(0, 2**63, k))


def drift_factors(cfg: dict, seed: int, call: int, n_platforms: int) -> np.ndarray:
    """Lognormal cost factors of the ``call``-th decision, (2, steps,
    platforms): the telemetry drift the controller prices afresh each time.
    Row 0 scales compute, row 1 fetch; each step's factor has that step's
    own calibrated spread (the sigma of its ``compute`` and ``fetch`` in the
    configuration), so the observed medians move as far as one spread of
    the distribution they were calibrated from."""
    sig = np.array([[s["compute"][1] for s in cfg["workflow"]],
                    [s["fetch"][1] for s in cfg["workflow"]]])[:, :, None]
    z = rng(seed, 6, call).standard_normal(sig.shape[:2] + (n_platforms,))
    return np.exp(sig * z)


def sample(n: int, k: int, seed: int, must=()) -> list:
    """Up to ``k`` distinct indices below ``n`` drawn from the seed, always
    holding ``must``; sorted."""
    picked = list(dict.fromkeys(int(i) for i in must))
    rest = [i for i in rng(seed, 7).permutation(n).tolist() if i not in picked]
    return sorted(picked + rest[: max(0, k - len(picked))])
