"""The one traffic generator: a mix file of parameters → a request plan.

A mix (``traffic/<name>.json``) holds only data:

* ``loop``: ``"open"`` (requests fall due on a schedule, whatever the
  server does) or ``"closed"`` (one client sends its next request when the
  previous one has returned);
* ``rate_per_s`` (open loop): arrivals per second, one every 1/rate s;
* ``burst`` (open loop, optional): requests that fall due together at each
  arrival;
* ``records``: request sizes, ``{"dist": "fixed", "n": 65536}`` or
  ``{"dist": "log_blocks", "lo": 1, "hi": 1024, "block": 16}`` (below);
* ``pool``: distinct requests made in set-up; the window sends them in
  turn and starts again from the first when it needs more (bounds host
  memory: 64 frames of 65,536 x 19 records are 320 MB); under
  ``log_blocks`` a multiple of ``block``, so that every cycle through the
  pool sends whole blocks;
* ``check_requests`` (optional): how many of the requests served in the
  window the reference checks, drawn from the seed once the window has
  closed; all of them where absent.

Everything is drawn from the run's ``--seed``: the same seed gives the same
sizes, arrival times and records.  Warm-up requests come from a stream of
their own, so the window never serves a record set that set-up served.

Variable sizes give every seed the same work.  ``log_blocks`` spaces
``block`` sizes evenly in log between ``lo`` and ``hi``,
``round(exp(ln lo + i/(block-1) * (ln hi - ln lo)))`` for i = 0 .. block-1
(1, 2, 3, 4, 6, 10, 16, 25, 40, 64, 102, 161, 256, 406, 645, 1024 for
(1, 1024, 16): 2,765 records), and every ``block`` consecutive requests
hold exactly these sizes; the seed draws only their order, one permutation
a block.  Any two seeds then send the same records in every whole block,
and a window's total differs between seeds by less than one block's sum.
Sizes drawn one by one would make the records a window sends, and so a
rate that counts them, depend on the seed: on [1, 1024] log-uniform sizes
have a coefficient of variation of 1.57, so over 7,650 sends the mean size
alone moves by 1.8% from seed to seed.  That distribution is refused.
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


def block_sizes(spec: dict) -> list[int]:
    """The sizes every block of a ``log_blocks`` spec holds, smallest first."""
    lo, hi, k = int(spec["lo"]), int(spec["hi"]), int(spec["block"])
    if not 1 <= lo <= hi or k < 2:
        raise ValueError(f"log_blocks needs 1 <= lo <= hi and block >= 2, not {spec}")
    a, b = math.log(lo), math.log(hi)
    return [int(round(math.exp(a + i / (k - 1) * (b - a)))) for i in range(k)]


def block_len(spec: dict) -> int:
    """Requests in one block of sizes: the fewest that hold every size."""
    return int(spec["block"]) if spec["dist"] == "log_blocks" else 1


def sizes(spec: dict, gen: np.random.Generator, n: int) -> list[int]:
    """``n`` request sizes drawn from the mix's ``records`` spec."""
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["n"])] * n
    if dist == "log_blocks":
        block = block_sizes(spec)
        order = [gen.permutation(block) for _ in range(-(-n // len(block)))]
        return [int(x) for b in order for x in b][:n]
    if dist == "log_uniform":
        raise ValueError("records dist 'log_uniform' is refused: sizes drawn one by one "
                         "make the records a window sends depend on the seed; use "
                         "'log_blocks', which gives every seed the same sizes in every block")
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
    b = block_len(mix["records"])
    if int(mix["pool"]) % b:
        raise ValueError(f"pool {mix['pool']} is not a multiple of block {b}: "
                         "the window would cycle through broken blocks")
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
    """Sizes for ``n`` warm-up requests: the mix's own distribution in whole
    blocks, so set-up compiles exactly the shapes the window will use, with
    the largest request first; the first :func:`block_len` requests hold
    every size."""
    spec = mix["records"]
    out = sizes(spec, rng(seed, WARMUP, SIZES), n)
    head = out[:block_len(spec)]
    if head:
        i = head.index(max(head))
        out[0], out[i] = out[i], out[0]
    return out
