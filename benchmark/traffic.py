"""The one general traffic generator: a mix file's parameters and a seed in,
a schedule of requests out. No JAX, so the load generator's process can use
it too.

A cell's schedule is one fixed cycle of (gap, prompt length, answer length)
triples: lengths are the stratified quantiles of the stated distributions,
gaps (open loop) the stratified quantiles of the exponential distribution (a
Poisson process's gaps) scaled to the rate, all put in one order that is a
constant of this file (``_ORDER``), the same for every mix and seed. The run's seed makes the prompts' token ids (and,
in the harness, the weights); it does not move the schedule. That is
measured, not assumed: on this system two runs of one seed agree within 0.3%
on the per-token time, while a free permutation per seed moved it by 9% and a
rotation of the cycle by 7% (PERF.md, Findings), so an order drawn from the
seed changes the work. The ramp is the stretch of the cycle just before the
window. Closed loop: ``clients`` callers each send their next request when
the last one ends, taking requests off the cycle in order.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any

import numpy as np

_MASK = (1 << 63) - 1
_ORDER = 0  # the stream that shuffles the cycle: a constant, not a parameter


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole-number seed."""
    salt = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & _MASK, salt])


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles ``(i + 0.5) / n`` of ``dist``,
    clipped to ``[min, max]``: the same multiset whatever the seed."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        unit = NormalDist()
        raw = [math.exp(mu + sigma * unit.inv_cdf(q)) for q in qs]
    elif kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        raw = [lo + q * (hi - lo) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    return [int(min(hi, max(lo, round(value)))) for value in raw]


def exponential_gaps(n: int, mean: float) -> list[float]:
    """``n`` gaps at the mid-quantiles of the exponential distribution,
    scaled so that their mean is exactly ``mean``."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = mean * n / sum(gaps)
    return [g * scale for g in gaps]


def build_schedule(mix: dict, load: dict, vocab: int, seed: int,
                   seconds: float) -> dict[str, Any]:
    """-> ``{"loop", "ramp_s", "seconds", "clients", "requests": [...]}``.
    A request is ``{"id", "due", "prompt", "max_tokens", "measured"}`` with
    ``due`` in seconds from the start of the ramp (open loop; 0.0 in a
    closed loop, where order alone matters)."""
    loop = mix["loop"]
    ramp_s = float(mix.get("ramp_s", 0.0))
    if loop == "open":
        n = int(round(float(load["rate_rps"]) * seconds))
    elif loop == "closed":
        n = int(load["requests"])
    else:
        raise ValueError(f"unknown loop {loop!r}")
    if n < 1:
        raise ValueError("the window would hold no request")
    # the cycle, in the one order every mix and seed gets
    order = rng_for(_ORDER, "order")
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outputs = quantile_lengths(mix["output_tokens"], n)
    order.shuffle(prompts)
    order.shuffle(outputs)
    picks: list[tuple[int, float, bool]] = []  # (cycle index, due, measured)
    if loop == "open":
        gaps = exponential_gaps(n, seconds / n)
        order.shuffle(gaps)
        due = ramp_s + gaps[0] / 2.0
        first = due
        for i in range(n):  # the window: one whole turn of the cycle
            picks.append((i, due, True))
            due += gaps[(i + 1) % n]
        due, k = first, 1
        while True:  # the ramp: the cycle run backwards from the window's start
            due -= gaps[(1 - k) % n]
            if due < 0.0:
                break
            picks.insert(0, ((-k) % n, due, False))
            k += 1
    else:
        picks = [(i, 0.0, True) for i in range(n)]
    tokens = rng_for(seed, "tokens")
    requests = [{
        "id": rid, "due": float(due), "max_tokens": outputs[i],
        # ids below the vocabulary; 0..2 left out (pad/bos/eos by custom)
        "prompt": tokens.integers(3, vocab, prompts[i]).tolist(),
        "measured": measured,
    } for rid, (i, due, measured) in enumerate(picks)]
    return {
        "loop": loop, "ramp_s": ramp_s, "seconds": float(seconds),
        "clients": int(load.get("clients", 0)), "requests": requests,
    }
