"""Open-loop request schedules from a traffic file and a seed.

One generator serves every traffic mix; a mix is a data file of parameters
(``bench/traffic/<name>.json``):

``arrivals``
    ``mean_rate`` (requests/s) and a list of ``phases``, each
    ``{"seconds": s, "rate_x": r}``, repeated until the window is covered.
    A phase holds ``round(mean_rate * r * s)`` arrivals, placed uniformly at
    random inside it: a Poisson process given its count.  The phase shape is
    fixed by the file, never by the seed, so every seed sees the same bursts.
``prompt`` / ``output``
    lognormal lengths ``{"median", "sigma", "min", "max"}`` (clipped), as in
    the conversation traces: the n requests of a run take the n quantiles of
    that law at (k + 1/2) / n, in an order drawn from the seed.  Every seed
    serves the same set of sizes, so seeds reorder the work and do not
    change its amount.
``order_seed``
    where given, the order of the sizes is drawn from it and not from the
    run's seed: every seed then serves the same sizes in the same order,
    and only arrival times and token ids change.  A window that ends
    inside long requests counts fewer of their tokens, so with the order
    drawn from the run's seed the tokens a window completes move with the
    seed.
``max_total``
    prompt + output never exceeds it (the output is cut).
``fill_batch``
    where true, as many extra requests as the engine has slots, from the
    same laws, are admitted before the window opens (the batch starts full).

Token ids are uniform over ``[0, vocab)``.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Arrival:
    rid: int
    due: float              # seconds from the window's start (<= 0: fill)
    prompt: np.ndarray      # token ids
    max_new_tokens: int


def lognormal_quantiles(n: int, law: dict) -> np.ndarray:
    """The n mid-quantiles of a clipped lognormal law, ascending."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((k + 0.5) / n) for k in range(n)])
    x = law["median"] * np.exp(law["sigma"] * z)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def phase_times(arrivals: dict, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
    """Arrival times in [0, seconds): fixed counts per phase, uniform
    positions inside each phase."""
    out = []
    t = 0.0
    rate = float(arrivals["mean_rate"])
    while t < seconds:
        for ph in arrivals["phases"]:
            if t >= seconds:
                break
            dur = min(float(ph["seconds"]), seconds - t)
            n = int(round(rate * float(ph["rate_x"]) * dur))
            out.append(np.sort(t + rng.uniform(0.0, dur, n)))
            t += dur
    return np.concatenate(out) if out else np.zeros(0)


def mean_rate_of(arrivals: dict) -> float:
    """Offered requests per second over one period of the phases."""
    sec = sum(float(p["seconds"]) for p in arrivals["phases"])
    return float(arrivals["mean_rate"]) * sum(
        float(p["seconds"]) * float(p["rate_x"])
        for p in arrivals["phases"]) / sec


def schedule(traffic: dict, seconds: float, seed: int, vocab: int,
             n_fill: int = 0) -> list[Arrival]:
    """The run's requests: ``n_fill`` fill requests (due 0, admitted before
    the window) first, then the window's open-loop arrivals in time order."""
    rng = np.random.default_rng(seed % 2**64)
    times = phase_times(traffic["arrivals"], seconds, rng)
    n = n_fill + len(times)
    if n == 0:
        return []
    order = (np.random.default_rng(int(traffic["order_seed"]))
             if "order_seed" in traffic else rng)
    prompts = order.permutation(lognormal_quantiles(n, traffic["prompt"]))
    outputs = order.permutation(lognormal_quantiles(n, traffic["output"]))
    cap = int(traffic.get("max_total", 0))
    due = np.concatenate([np.zeros(n_fill), times])
    out = []
    for i in range(n):
        p, o = int(prompts[i]), int(outputs[i])
        if cap:
            o = max(1, min(o, cap - p))
        out.append(Arrival(rid=i, due=float(due[i]),
                           prompt=rng.integers(0, vocab, p, dtype=np.int64),
                           max_new_tokens=o))
    return out


def expected_output_tokens(law: dict, n: int = 4096) -> float:
    """Mean output length of a law, from its quantiles."""
    return float(np.mean(lognormal_quantiles(n, law)))


def seed_key_int(seed: int) -> int:
    """A 31-bit integer for JAX's PRNG from any whole-number seed."""
    return int(np.random.default_rng((seed % 2**64, 1)).integers(0, 2**31 - 1))

