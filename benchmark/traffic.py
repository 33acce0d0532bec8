"""The one general traffic generator: a mix is a data file of parameters.

Arrivals and lengths follow ``elephas_tpu/fleet/traffic.py`` (Lewis
thinning against a rate with periodic bursts; clipped lognormal lengths),
without its tenants, deadlines and diurnal wave. What differs is where the
randomness comes from. The mix's own ``shape_seed`` fixes the arrival
times and the sequence of (prompt length, output budget) pairs, so every
run offers the same work at the same instants; the run's ``--seed`` only
permutes the pairs inside consecutive blocks of ``BLOCK`` requests, and
draws the tokens. Any stretch of a run (its window, whatever a closed loop
gets through) then holds nearly the same multiset of sizes for every seed,
in another order, and runs with different seeds differ little more than
two runs of one seed. (A permutation over the whole run was tried first:
the window's share of the long requests then changed with the seed, and
tokens/s of the closed loop spread by 4%, PERF.md.)

Keys of a mix (``<dir>/traffic/<name>.json``):

    driver          the driver module under drivers/ that runs it
    shape_seed      fixes arrivals and the multiset of lengths
    prompt_tokens   {"median", "sigma", "min", "max"}  clipped lognormal
    output_tokens   {"median", "sigma", "min", "max"}  clipped lognormal
    arrivals        open loop: {"rate_per_s", "burst_amp", "burst_every_s",
                    "burst_width_s"} (bursts optional, periodic)
    callers         closed loop: how many callers, each sending its next
                    request when its last completes
    pool            closed loop: how many pairs are drawn before cycling
"""

import math

import numpy as np


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    draw = np.exp(math.log(spec["median"])
                  + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(np.int64)


def _rate(t: float, arr: dict) -> float:
    rate = arr["rate_per_s"]
    every = arr.get("burst_every_s")
    if arr.get("burst_amp") and every:
        if (t % every) < arr.get("burst_width_s", 0.0):
            rate *= 1.0 + arr["burst_amp"]
    return rate


def arrival_times(mix: dict, horizon_s: float) -> list:
    """Arrival times in ``[0, horizon_s)`` from the mix's ``shape_seed``
    alone: a longer horizon extends the same sequence."""
    arr = mix["arrivals"]
    rng = np.random.default_rng([int(mix["shape_seed"]), 0])
    rate_max = arr["rate_per_s"] * (1.0 + arr.get("burst_amp", 0.0))
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate_max)
        keep = rng.random() < _rate(t, arr) / rate_max
        if t >= horizon_s:
            return out
        if keep:
            out.append(t)


BLOCK = 16


def length_pairs(mix: dict, n: int, seed: int) -> list:
    """``n`` (prompt length, output budget) pairs: the sequence comes from
    ``shape_seed`` (a larger ``n`` extends it); ``seed`` permutes it inside
    each block of ``BLOCK``."""
    rng = np.random.default_rng([int(mix["shape_seed"]), 1])
    # drawn in blocks so that the first n are the same for every n
    block = 256
    blocks = max(1, -(-n // block))
    prompts = np.concatenate(
        [_lengths(rng, mix["prompt_tokens"], block) for _ in range(blocks)])
    rng = np.random.default_rng([int(mix["shape_seed"]), 2])
    outputs = np.concatenate(
        [_lengths(rng, mix["output_tokens"], block) for _ in range(blocks)])
    rng = np.random.default_rng([int(seed), 3])
    order = np.concatenate(
        [lo + rng.permutation(min(BLOCK, n - lo))
         for lo in range(0, n, BLOCK)] or [np.zeros(0, np.int64)])
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def prompt_tokens(seed: int, index: int, length: int, vocab: int):
    """The tokens of request ``index`` (negative for the warm-up and
    check prompts): uniform over the vocabulary."""
    rng = np.random.default_rng([int(seed), 4 if index >= 0 else 7,
                                 abs(int(index))])
    return rng.integers(0, vocab, size=length).astype(np.int32)


def open_loop_schedule(mix: dict, seed: int, horizon_s: float) -> list:
    """Requests ``{"index", "due_s", "prompt_len", "max_new"}`` due in
    ``[0, horizon_s)``, in arrival order."""
    times = arrival_times(mix, horizon_s)
    pairs = length_pairs(mix, len(times), seed)
    return [{"index": i, "due_s": t, "prompt_len": p, "max_new": m}
            for i, (t, (p, m)) in enumerate(zip(times, pairs))]


def closed_loop_pool(mix: dict, seed: int) -> list:
    """The pairs the callers of a closed loop draw from, in order (cycled
    when a run outlasts the pool)."""
    return length_pairs(mix, int(mix["pool"]), seed)
