"""The one traffic generator: a mix file of parameters → a request plan.

A mix (``traffic/<name>.json``) holds only data:

* ``loop``: ``"open"`` (requests fall due on a schedule, whatever the
  server does) or ``"closed"`` (one client sends its next request when the
  previous one has returned);
* ``rate_per_s`` (open loop): arrivals per second, one every 1/rate s;
* ``burst`` (open loop, optional): requests that fall due together at each
  arrival;
* ``records``: request sizes, ``{"dist": "fixed", "n": 65536}`` or
  ``{"dist": "log_uniform", "lo": 1, "hi": 1024}``;
* ``pool``: distinct requests made in set-up; the window sends them in
  turn and starts again from the first when it needs more (bounds host
  memory: 64 frames of 65,536 x 19 records are 320 MB);
* ``check_requests`` (optional): how many of the requests served in the
  window the reference checks, drawn from the seed once the window has
  closed; all of them where absent.

Everything is drawn from the run's ``--seed``: the same seed gives the same
sizes, arrival times and records.  Warm-up requests come from a stream of
their own, so the window never serves a record set that set-up served.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# seed-sequence tags: each use of the seed draws from a stream of its own; a
# changed tag changes every seed's inputs, so they stay as they are
WINDOW, WARMUP, SIZES, CHECK, MODEL = 0, 1, 2, 4, 5


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A generator for one use of ``seed``; any non-negative int works."""
    return np.random.default_rng([int(seed), *tags])


def sizes(spec: dict, gen: np.random.Generator, n: int) -> list[int]:
    """``n`` request sizes drawn from the mix's ``records`` spec."""
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["n"])] * n
    if dist == "log_uniform":
        lo, hi = math.log(spec["lo"]), math.log(spec["hi"])
        return [int(round(math.exp(x))) for x in gen.uniform(lo, hi, n)]
    raise ValueError(f"unknown records dist {dist!r}")


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one run sends: request sizes and, for an open loop, due times
    in seconds from the window's start."""

    loop: str
    sizes: tuple[int, ...]        # of the pool's distinct requests
    due_s: tuple[float, ...] | None   # open loop: one per request sent
    check_requests: int | None    # served requests the reference checks; None: all


def plan(mix: dict, seed: int, seconds: float) -> Plan:
    """The requests of one window of ``seconds`` under ``mix``."""
    loop = mix["loop"]
    if loop == "open":
        rate = float(mix["rate_per_s"])
        burst = int(mix.get("burst", 1))
        t = np.arange(max(1, int(math.floor(rate * seconds)))) / rate
        due = tuple(float(x) for x in np.repeat(t, burst))
        n = min(len(due), int(mix["pool"]))
    elif loop == "closed":
        due = None
        n = int(mix["pool"])
    else:
        raise ValueError(f"unknown loop {loop!r}")
    k = mix.get("check_requests")
    return Plan(loop, tuple(sizes(mix["records"], rng(seed, SIZES), n)), due,
                None if k is None else int(k))


def check_positions(plan: Plan, seed: int, n_served: int) -> list[int]:
    """Which of the ``n_served`` requests the reference checks."""
    if plan.check_requests is None or plan.check_requests >= n_served:
        return list(range(n_served))
    pick = rng(seed, CHECK).choice(n_served, plan.check_requests, replace=False)
    return sorted(int(i) for i in pick)


def warmup_sizes(mix: dict, seed: int, n: int) -> list[int]:
    """Sizes for ``n`` warm-up requests: the mix's own distribution, so set-up
    compiles exactly the shapes the window will use, and always its largest
    request first."""
    spec = mix["records"]
    out = sizes(spec, rng(seed, WARMUP, SIZES), n)
    if spec["dist"] != "fixed" and out:
        out[0] = int(spec["hi"])
    return out
