"""The one traffic generator: reads a traffic file and makes a run's load.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    {"hi": {"rate_per_s": 5.0}, "lo": {"backlog": 2}}

High priority is open loop: a Poisson process of rate ``rate_per_s``
over the window, conditioned on its count ``round(rate x seconds)``, so
its arrival times are that many uniform draws, sorted. The draw is one
fixed realization, the same for every ``--seed``: the seed changes the
prompts and the weights, not when requests come. (A realization per seed
moves the 95th percentile of latency by 10-25% from seed to seed through
the arrivals alone; PERF.md, Findings.) Low priority is closed loop:
``backlog`` clients, each resubmitting the moment its previous request
is answered.

Prompt tokens are uniform over the vocabulary, a pure function of (seed,
role, phase, request number), so the reference can draw them again.
"""
from __future__ import annotations

import math

import numpy as np

from benchlib.weights import seed32

ROLES = ("hi", "lo")

#: the key of the one Poisson realization every run replays
ARRIVALS_KEY = 20231117


def hi_arrivals(rate: float, seconds: float) -> list:
    """Arrival times (seconds from the window's start) of the open-loop
    high-priority stream."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng([ARRIVALS_KEY, n])
    return [float(t) for t in np.sort(rng.uniform(0.0, seconds, n))]


def tokens(seed: int, role: str, phase: int, k: int, vocab: int,
           batch: int, seq: int) -> np.ndarray:
    """Prompt tokens [batch, seq] of request ``k`` of ``role`` in
    ``phase`` (0 for set-up, 1 for the measured window)."""
    rng = np.random.default_rng([seed32(seed), ROLES.index(role) + 2,
                                 phase, k])
    return rng.integers(0, vocab, (batch, seq), dtype=np.int32)


def check_traffic(t: dict) -> dict:
    """Refuse a traffic file the generator cannot serve."""
    hi, lo = t.get("hi", {}), t.get("lo", {})
    rate = float(hi.get("rate_per_s", 0))
    if not (rate > 0 and math.isfinite(rate)):
        raise ValueError(f"hi rate_per_s must be > 0, got {rate!r}")
    if int(lo.get("backlog", -1)) < 0:
        raise ValueError("lo backlog must be >= 0")
    return t
