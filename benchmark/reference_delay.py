"""The reference's straggler model, restated with no program code.

What the ASYNC drivers do to simulate a production cluster (arXiv:1907.08526
Figs 7-8; ``SparkASGDThread.scala:121-138``: who is late, ``:284-309``: how
late), in plain Python and ``numpy``:

- ``coeff`` -1, the cloud: ``round(0.25 n)`` of ``n`` workers are late, at
  the ids ``0, 4, 8, ...``.  Of those, ``round(0.8 x their number)`` are of
  the *normal* class and the first of the id sequence that are left over
  are the *long tail*.  A late worker sleeps in front of EVERY task it is
  given once the calibration is over: ``U(2.5, 10)`` x the scale in the long
  tail, ``U(1.5, 2.5)`` x the scale in the normal class, to whole
  milliseconds.
- ``coeff`` > 0, the controlled delay: worker 0 alone sleeps ``coeff`` x the
  scale, every task.
- the scale is the run's own mean task time over its calibration; nobody
  sleeps before it is known.

One departure, which this repository's program makes and states
(``engine/straggler.py``): the reference draws from an unseeded
``java.util.Random``; here the uniforms come from ``numpy``'s
``default_rng(seed)``, ONE stream a run, one draw a delayed task in the
order the tasks were built, so that a run's schedule can be said again from
its seed.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

NORMAL, LONG_TAIL = "normal", "long_tail"
#: the multipliers' ranges, by class
RANGE = {NORMAL: (1.5, 2.5), LONG_TAIL: (2.5, 10.0)}


def late_workers(n: int, coeff: float = -1.0) -> Dict[int, str]:
    """Who is late among ``n`` workers, and in which class."""
    if coeff == 0:
        return {}
    if coeff != -1:
        return {0: NORMAL} if coeff > 0 else {}
    late = _half_up(0.25 * n)
    normal = _half_up(0.8 * late)
    ids = [4 * c for c in range(late)]
    head = late - normal
    return {**{w: LONG_TAIL for w in ids[:head]},
            **{w: NORMAL for w in ids[head:]}}


def _half_up(x: float) -> int:
    """Java's ``Math.round``: halves go up.  Python's ``round`` sends a
    half to the even neighbour, so a program that counts with it marks one
    worker fewer where ``0.25 n`` lands on a half whose floor is even: 2,
    10, 18, 26 workers, every count that is 2 over a multiple of 8 (no
    cell has such a count; PERF.md section 7 says so)."""
    return int(math.floor(x + 0.5))


def sleeps(seed: int, scale_ms: float, order: Sequence[int], n: int,
           coeff: float = -1.0) -> List[float]:
    """Each delayed task's sleep in ms, for the late workers' tasks in the
    order they were built (``order``: their worker ids).  A worker that is
    not late has no place in ``order``: naming one is an error."""
    classes = late_workers(n, coeff)
    rng = np.random.default_rng(seed)
    out = []
    for wid in order:
        if coeff != -1:
            if classes.get(wid) is None:
                raise ValueError(f"worker {wid} is never late at {coeff}")
            out.append(float(_half_even(coeff * scale_ms)))
            continue
        lo, hi = RANGE[classes[wid]]
        c = rng.random() * (hi - lo) + lo
        out.append(float(_half_even(c * scale_ms)))
    return out


def _half_even(x: float) -> int:
    """To whole milliseconds, a half to the even one (a product of doubles
    lands on a half with probability zero)."""
    return int(np.rint(x))


def split(log: Sequence[Tuple[int, float]]) -> Tuple[List[int], List[float]]:
    """A run's log of ``(worker, delay_ms)``, one entry a task built, to
    the delayed tasks' workers and sleeps, in order."""
    hit = [(int(w), float(ms)) for w, ms in log if ms > 0]
    return [w for w, _ in hit], [ms for _, ms in hit]
