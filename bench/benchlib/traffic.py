"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic``; this module turns it and a seed into the requests of
a run.

Parameters (all required unless marked):

``loop``         ``"closed"``: ``outstanding`` requests are kept in
                 flight, each completion replaced at once by a new one.
                 ``"open"``: requests are sent on a schedule whatever the
                 server does (``arrivals`` ``"poisson"`` at ``rate_per_s``).
``buckets``      the batch buckets the engine compiles and serves.
``deadline_ms``  each request's soft batching delay (null: none, a
                 partial bucket is flushed only at drain).
``pool``         distinct images; request ``i`` sends ``image[i]`` of
                 the pool.  Seeded, so the reference runs once per image.
``warmup_s``     traffic of the same kind sent before the window opens,
                 counted as set-up (the host path in steady state when
                 the window opens).

A Poisson schedule is drawn as a fixed number of arrivals,
``round(rate * seconds)``, placed uniformly at random over the window:
the Poisson process conditioned on its count.  Every seed then sends the
same amount of work, in another order and at other moments.
"""
from __future__ import annotations

import dataclasses

import numpy as np

LOOPS = ("closed", "open")


class TrafficError(ValueError):
    pass


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


@dataclasses.dataclass(frozen=True)
class Mix:
    loop: str
    buckets: tuple
    pool: int
    warmup_s: float
    deadline_ms: float | None = None
    outstanding: int = 0
    rate_per_s: float = 0.0

    @classmethod
    def parse(cls, d: dict) -> "Mix":
        loop = d.get("loop")
        if loop not in LOOPS:
            raise TrafficError(f"loop must be one of {LOOPS}, got {loop!r}")
        buckets = tuple(sorted(int(b) for b in d["buckets"]))
        if not buckets or buckets[0] < 1:
            raise TrafficError(f"buckets {buckets}")
        mix = cls(loop=loop, buckets=buckets, pool=int(d["pool"]),
                  warmup_s=float(d["warmup_s"]),
                  deadline_ms=(None if d.get("deadline_ms") is None
                               else float(d["deadline_ms"])),
                  outstanding=int(d.get("outstanding", 0)),
                  rate_per_s=float(d.get("rate_per_s", 0.0)))
        if mix.pool < 1 or mix.warmup_s < 0:
            raise TrafficError(f"pool {mix.pool}, warmup_s {mix.warmup_s}")
        if loop == "closed" and mix.outstanding < 1:
            raise TrafficError("a closed loop needs outstanding >= 1")
        if loop == "open":
            if d.get("arrivals") != "poisson":
                raise TrafficError(f"arrivals {d.get('arrivals')!r}: "
                                   f"only 'poisson'")
            if mix.rate_per_s <= 0:
                raise TrafficError("an open loop needs rate_per_s > 0")
        return mix


@dataclasses.dataclass(frozen=True)
class Schedule:
    """What one run sends.  ``due_s``: send times relative to the
    window's opening (negative ones fall in the warm-up), open loop
    only.  ``images``: the pool index of each request in send order (a
    closed loop cycles through it)."""
    mix: Mix
    due_s: np.ndarray
    images: np.ndarray


def poisson_times(rng, rate: float, start: float, length: float):
    n = int(round(rate * length))
    return np.sort(start + rng.uniform(0.0, length, n))


def schedule(mix: Mix, seed: int, seconds: float) -> Schedule:
    rng = rng_for(seed, 1)
    if mix.loop == "open":
        due = np.concatenate([
            poisson_times(rng, mix.rate_per_s, -mix.warmup_s, mix.warmup_s),
            poisson_times(rng, mix.rate_per_s, 0.0, seconds)])
        images = rng.integers(0, mix.pool, len(due))
    else:
        due = np.zeros(0)
        images = np.concatenate([rng.permutation(mix.pool)
                                 for _ in range(4)])
    return Schedule(mix, due, images)
