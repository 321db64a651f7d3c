"""An open loop's arrival schedule and what its order checks read,
in plain NumPy and plain lists of instants: imports nothing of
``lux_tpu``.

The schedule is a Poisson process: cumulative exponential gaps of mean
``1 / rate_qps`` from a seeded generator, in seconds from the start of
the loop.  The checks take instants on one clock (the caller's) and
count; none of them holds a time limit, so a stall of the machine
moves none of them.
"""

from __future__ import annotations

import bisect
import itertools

import numpy as np

CHUNK = 1024


def schedule(rate_qps: float, seed: int, n: int) -> np.ndarray:
    """The first ``n`` arrival instants (seconds from the loop's
    start).  A prefix of every longer schedule of the same seed."""
    return np.fromiter(arrivals(rate_qps, seed), float, count=n)


def arrivals(rate_qps: float, seed: int):
    """The arrival instants, one after another, without end."""
    if not rate_qps > 0:
        raise ValueError(f"rate_qps must be positive, got {rate_qps}")
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    at = 0.0
    while True:
        for gap in rng.exponential(1.0 / rate_qps, size=CHUNK):
            at += float(gap)
            yield at


def until(rate_qps: float, seed: int, until_s: float) -> list:
    """The arrival instants up to ``until_s``."""
    return list(itertools.takewhile(lambda at: at <= until_s,
                                    arrivals(rate_qps, seed)))


def arrivals_missed(rate_qps: float, seed: int, until_s: float,
                    submitted) -> int:
    """Scheduled arrivals up to ``until_s`` that were not submitted,
    plus submissions that stand at another instant than the
    schedule's: ``submitted`` is the list of the scheduled instants
    the generator says it served, in its order."""
    want = until(rate_qps, seed, until_s)
    got = list(submitted)
    wrong = sum(1 for w, g in zip(want, got) if abs(w - g) > 1e-9)
    return wrong + abs(len(want) - len(got))


def delivered_late(retired, received, turn_starts) -> int:
    """Responses that reached the caller only after a LATER turn had
    started: those with a turn start strictly between the instant the
    program retired the query (``retired[i]``) and the instant the
    caller held the response (``received[i]``).  A program that hands
    a turn's responses over before the next turn starts reads 0; one
    that holds them to the end of a drain reads every response but
    the last turn's."""
    starts = sorted(turn_starts)
    late = 0
    for t_ret, t_got in zip(retired, received):
        i = bisect.bisect_right(starts, t_ret)
        if i < len(starts) and starts[i] < t_got:
            late += 1
    return late


def fifo_inversions(started) -> int:
    """Queries that took a column before a query submitted earlier:
    ``started`` lists the queries' submit positions (0, 1, 2, ... in
    the order they were submitted) in the order they took a column.
    A query counts if any later entry is smaller."""
    inversions, least = 0, float("inf")
    for pos in reversed(list(started)):
        if pos > least:
            inversions += 1
        least = min(least, pos)
    return inversions

