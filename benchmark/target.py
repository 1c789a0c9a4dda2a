"""From a trajectory to the paper's metric of record.

``updates_to_target`` is the accepted-update count at which the objective
first falls to ``target_fraction`` of its value at ``w = 0``, interpolated
log-linearly between the two snapshots around the crossing;
``time_to_target_s`` divides it by the fenced rate of the whole window
(trajectory timestamps are host dispatch times and are not used).  The
arithmetic is ``bench.py``'s ``t_hit = k_hit * elapsed_s / accepted``,
with the crossing interpolated where ``bench.py`` took the snapshot after
it.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def snapshot_updates(n_snapshots: int, printer_freq: int, accepted: int,
                     per_snapshot: int = 1) -> List[int]:
    """Accepted updates behind each trajectory snapshot.  The solvers keep
    the model at ``w = 0``, then the model after update ``j * printer_freq
    + 1`` for ``j = 0, 1, ...`` (a snapshot is taken when the count
    *before* the apply is a multiple of ``printer_freq``), then the final
    model.  ``per_snapshot`` is the updates one counted step stands for
    (``num_workers`` in synchronous mode, whose counter is rounds)."""
    if n_snapshots < 2:
        raise ValueError("a trajectory has at least w=0 and the final model")
    mid = [
        (j * printer_freq + 1) * per_snapshot for j in range(n_snapshots - 2)
    ]
    return [0] + mid + [accepted]


def updates_to_target(updates: Sequence[float], objective: Sequence[float],
                      target: float) -> Optional[float]:
    """First crossing of ``target``, log-linear between snapshots; None if
    the trajectory never gets there."""
    for i, f in enumerate(objective):
        if f <= target:
            if i == 0:
                return float(updates[0])
            k0, k1 = updates[i - 1], updates[i]
            f0 = objective[i - 1]
            if not (f0 > target and f > 0 and f0 > f):
                return float(k1)
            share = (math.log(f0) - math.log(target)) / (
                math.log(f0) - math.log(f)
            )
            return k0 + (k1 - k0) * share
    return None


def time_to_target_s(updates_hit: float, accepted: int,
                     elapsed_s: float) -> float:
    return updates_hit * elapsed_s / accepted
