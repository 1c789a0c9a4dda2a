"""How old a worker's history is when it is replaced, restated with no
program code.

Under ASAGA a worker's slice of the history table is rewritten only when
one of ITS results is accepted, and ``alpha_bar`` carries that slice's
gradients until then.  The age of a slice at a commit is the number of
accepted updates since its worker's previous commit: with every worker
equally fast it is the worker count; under the straggler model
(``reference_delay``: who is late, in which class) a late worker's slice
grows old, and the healthy ones' a little younger.

From a run's accept order alone (the worker id of every accepted update,
in the order the server applied them), in plain Python:

- an accept of a worker that has not been accepted before has no age;
- an age is counted only for accepts BEHIND the calibration's end (the
  first ``calibrated_at`` accepts come before it: nobody is late there
  and the two classes are one), but a worker's previous commit may lie in
  front of it;
- ``calibrated_at`` of 0 or None says the run ended inside its
  calibration: nothing is counted.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

HEALTHY = "healthy"


def ages(order: Sequence[int], calibrated_at: Optional[int]
         ) -> List[Tuple[int, int]]:
    """``(worker, age)`` of every counted accept, in the run's order."""
    if not calibrated_at:
        return []
    last: Dict[int, int] = {}
    out = []
    for i, wid in enumerate(order):
        before = last.get(wid)
        last[wid] = i
        if before is not None and i >= calibrated_at:
            out.append((wid, i - before))
    return out


def account(order: Sequence[int], late: Iterable[int],
            calibrated_at: Optional[int]) -> Dict[str, int]:
    """The four integers a run reports (``TrainResult.extras``), under the
    program's names: the ages' sum and count, by whether the worker is one
    of ``late``."""
    late = set(late)
    out = dict.fromkeys(("history_age_late_sum", "history_age_late_n",
                         "history_age_healthy_sum", "history_age_healthy_n"),
                        0)
    for wid, age in ages(order, calibrated_at):
        who = "late" if wid in late else "healthy"
        out[f"history_age_{who}_sum"] += age
        out[f"history_age_{who}_n"] += 1
    return out


def by_class(order: Sequence[int], classes: Mapping[int, str],
             calibrated_at: Optional[int]) -> Dict[str, Dict[str, float]]:
    """The distribution the four sums cannot show: count, mean, median,
    95th percentile (nearest rank) and maximum of the ages, by the class
    ``reference_delay.late_workers`` gives a worker (``healthy`` where it
    gives none)."""
    found: Dict[str, List[int]] = {}
    for wid, age in ages(order, calibrated_at):
        found.setdefault(classes.get(wid, HEALTHY), []).append(age)
    out = {}
    for name, values in sorted(found.items()):
        values.sort()
        n = len(values)
        out[name] = {
            "count": n, "mean": sum(values) / n,
            "p50": values[(n - 1) // 2],
            "p95": values[-(-95 * n // 100) - 1],  # ceil(0.95 n), from 1
            "max": values[-1],
        }
    return out
