"""The one traffic generator: turns a mix's parameters into inputs.

A mix (``traffic/<name>.json``) is data only:

- ``loop``: the service loop, ``loops/<loop>.py``: ``"open"`` (independent
  users; requests fall due on a schedule, whatever the server does) or
  ``"closed"`` (one client that sends its next request when the previous
  one returns).
- ``arrivals``: open loop only, the arrival process ``arrivals/<name>.py``
  (``"poisson"``), with its parameters beside it (``rate_per_s``).
- ``images_per_request``: images a request carries.
- ``max_batch``: the batcher's batch size the cell runs with.
- ``pool``: how many distinct images the run draws from.
- ``sides``: groups ``{"share", "lo", "hi"}``; a group's images have each
  side spread evenly over ``lo..hi`` pixels.

Every seed gets the same set of image sizes and of gaps between arrivals, in
another order, with other pixels: the seed changes which work comes when,
not how much there is.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

_SIZE_PAIRING_SEED = 0  # fixed, so the size set is the same for every seed


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the run's seed (any integer)."""
    return np.random.default_rng([stream, seed % 2**64])


def _apportion(total: int, shares: list) -> list:
    """Largest-remainder split of ``total`` by ``shares``."""
    raw = [total * s / sum(shares) for s in shares]
    out = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: out[i] - raw[i])[: total - sum(out)]:
        out[i] += 1
    return out


def pool_sizes(mix: dict) -> list:
    """``(h, w)`` of every pool image; the same list for every seed."""
    groups = mix["sides"]
    sizes = []
    pair = np.random.default_rng(_SIZE_PAIRING_SEED)
    for g, n in zip(groups, _apportion(mix["pool"], [g["share"] for g in groups])):
        span = g["hi"] - g["lo"] + 1
        sides = [g["lo"] + (2 * k + 1) * span // (2 * n) for k in range(n)]
        sizes += zip(sides, [sides[i] for i in pair.permutation(n)])
    return [(int(h), int(w)) for h, w in sizes]


def make_pool(mix: dict, channels: int, seed: int) -> list:
    """The run's distinct images, ``(C, h, w)`` float32 standard normal."""
    g = rng(seed, 1)
    return [g.standard_normal((channels, h, w), dtype=np.float32)
            for h, w in pool_sizes(mix)]


def _picks(mix: dict, g: np.random.Generator):
    """Pool indices, each pool image once per cycle, cycles shuffled."""
    while True:
        yield from g.permutation(mix["pool"]).tolist()


def open_schedule(mix: dict, seed: int, seconds: float, arrivals: Callable) -> tuple:
    """``(due_s, picks)`` of the requests due in ``[0, seconds)``, their
    times from the arrival process ``arrivals(mix, rng, seconds)``."""
    g = rng(seed, 2)
    due = np.asarray(arrivals(mix, g, seconds))
    due = due[due < seconds]
    it = _picks(mix, g)
    per = mix["images_per_request"]
    return due.tolist(), [[next(it) for _ in range(per)] for _ in due]


def closed_requests(mix: dict, seed: int):
    """Endless picks for the closed loop's client, one list per request."""
    it = _picks(mix, rng(seed, 2))
    per = mix["images_per_request"]
    while True:
        yield [next(it) for _ in range(per)]
