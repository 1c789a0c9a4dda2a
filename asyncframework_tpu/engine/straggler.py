"""Deterministic straggler / delay injection.

Parity: the ASYNC drivers' simulation of slow workers
(``SparkASGDThread.scala:121-138`` for cohort construction,
``:284-309`` for the injected sleeps):

- ``coeff > 0``: worker 0 sleeps ``coeff * avg_delay`` each round (a single
  deterministic straggler whose slowness scales with measured average task
  latency);
- ``coeff == -1`` ("cloud mode", long-tail): 25% of workers are stragglers --
  of those, 80% sleep ``U(1.5, 2.5) * avg_delay`` and the rest sleep
  ``U(2.5, 10) * avg_delay``; straggler worker ids follow the reference's
  ``c * 4`` spacing pattern;
- delays activate only after the calibration phase (first ``100 * num_workers``
  accepted updates measure ``avg_delay``).

Delta from the reference: the per-round multipliers draw from a seeded
``numpy`` Generator instead of an unseeded ``java.util.Random``, so runs are
reproducible; staleness on a real pod also arises naturally from compute-time
variance -- this module only *adds* controlled skew.

The model keeps an account of what it injected (:meth:`DelayModel.account`,
a run's ``TrainResult.extras``): plain sums on the one thread that builds a
run's tasks, nothing where it injects nothing.  Beside it, for a solver that
keeps per-worker state between a worker's commits (ASAGA's history slices),
how old that state was when it was replaced, by whether its worker is one of
the late ones (:meth:`DelayModel.book_history_age`): host integers on the
updater's thread.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def build_cloud_stragglers(num_workers: int) -> Tuple[List[int], List[int]]:
    """Reference cohort construction (``SparkASGDThread.scala:126-138``):
    ``length = round(0.25 * n)`` stragglers; first ``length - round(0.8*length)``
    of the ``c*4`` id sequence are long-tail, the rest normal."""
    length = int(round(0.25 * num_workers))
    length_normal = int(round(0.8 * length))
    length_long_tail = length - length_normal
    long_tail = [c * 4 for c in range(0, length_long_tail)]
    normal = [c * 4 for c in range(length_long_tail, length)]
    return normal, long_tail


@dataclass
class DelayModel:
    """Computes the injected delay (ms) for a worker in one round.

    :meth:`delay_ms` is called where a task is built, by ONE thread (an
    engine run's submitter): it draws from the seeded generator in call
    order and keeps the account without a lock."""

    coeff: float
    num_workers: int
    seed: int = 42
    avg_delay_ms: float = 0.0
    calibrated: bool = False
    #: the account: when the calibration ended (the run's accepted updates
    #: and seconds, as :meth:`calibrate` is told), the tasks given a delay
    #: and the sum of the sleeps they were given, by multiplier class
    calibrated_at_update: int = 0
    calibrated_at_s: float = 0.0
    delayed_tasks: int = 0
    sleep_ms: float = 0.0
    sleep_long_tail_ms: float = 0.0
    #: the age account (:meth:`book_history_age`): the updates between a
    #: worker's commits of its per-worker state, summed and counted by
    #: whether the worker is one of :attr:`stragglers`
    history_age_late_sum: int = 0
    history_age_late_n: int = 0
    history_age_healthy_sum: int = 0
    history_age_healthy_n: int = 0
    _rng: np.random.Generator = field(default=None, repr=False)  # type: ignore
    _normal: List[int] = field(default_factory=list)
    _long_tail: List[int] = field(default_factory=list)

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)
        if self.cloud_mode:
            self._normal, self._long_tail = build_cloud_stragglers(self.num_workers)

    @property
    def cloud_mode(self) -> bool:
        return self.coeff == -1

    @property
    def enabled(self) -> bool:
        return self.coeff != 0

    @property
    def stragglers(self) -> List[int]:
        """The workers this model ever delays, long tail first."""
        if self.cloud_mode:
            return self._long_tail + self._normal
        return [0] if self.coeff > 0 else []

    def long_tail(self, worker_id: int) -> bool:
        """Whether the worker's multipliers are the long tail's."""
        return worker_id in self._long_tail

    def worker_class(self, worker_id: int) -> str:
        """``long_tail``, ``normal`` (the two late classes) or ``healthy``."""
        if worker_id in self._long_tail:
            return "long_tail"
        return "normal" if worker_id in self.stragglers else "healthy"

    def calibrate(self, avg_delay_ms: float, at_update: int = 0,
                  at_s: float = 0.0) -> None:
        """Fix the average-delay scale after the measurement phase
        (``at_update``, ``at_s``: where the run stood, for the account)."""
        self.avg_delay_ms = avg_delay_ms
        self.calibrated = True
        self.calibrated_at_update = at_update
        self.calibrated_at_s = at_s

    def delay_ms(self, worker_id: int) -> float:
        """Delay to inject for this worker this round (0 before calibration)."""
        if not self.enabled or not self.calibrated:
            return 0.0
        if not self.cloud_mode:
            if worker_id == 0 and self.coeff > 0:
                return self._given(
                    float(round(self.coeff * self.avg_delay_ms)), False)
            return 0.0
        if worker_id in self._long_tail:
            c = self._rng.random() * 7.5 + 2.5
            return self._given(float(round(c * self.avg_delay_ms)), True)
        if worker_id in self._normal:
            c = self._rng.random() + 1.5
            return self._given(float(round(c * self.avg_delay_ms)), False)
        return 0.0

    def _given(self, delay_ms: float, long_tail: bool) -> float:
        if delay_ms > 0:
            self.delayed_tasks += 1
            self.sleep_ms += delay_ms
            if long_tail:
                self.sleep_long_tail_ms += delay_ms
        return delay_ms

    def book_history_age(self, worker_id: int, age: int) -> Optional[str]:
        """An accepted update replaces state of ``worker_id`` that was
        committed ``age`` accepted updates ago (ASAGA's updater: its
        history slice; the updater's thread, under the run's state lock).
        Booked only once somebody is late (before the calibration's end
        the two classes are one): returns the worker's class where it
        booked, None where it did not."""
        if not (self.enabled and self.calibrated):
            return None
        booked_as = self.worker_class(worker_id)
        if booked_as == "healthy":
            self.history_age_healthy_sum += age
            self.history_age_healthy_n += 1
        else:
            self.history_age_late_sum += age
            self.history_age_late_n += 1
        return booked_as

    def account(self, accepted_by_worker: Sequence[int]) -> Dict[str, object]:
        """What was injected, as scalars (``accepted_by_worker``: the
        run's accepted updates by worker id).  Every figure is 0 where the
        model is off or the run ended inside the calibration.  A task is
        counted where it is built: one that the run's end overtakes was
        given its sleep and never took it (at most one a straggler)."""
        on = self.enabled and self.calibrated
        accepted = sum(accepted_by_worker)
        return {
            "avg_delay_ms": self.avg_delay_ms if on else 0.0,
            "delay_calibrated_at_update":
                self.calibrated_at_update if on else 0,
            "delay_calibrated_at_s": self.calibrated_at_s if on else 0.0,
            "straggler_workers": len(self.stragglers),
            "delayed_tasks": self.delayed_tasks,
            "delay_sleep_s": self.sleep_ms / 1e3,
            "delay_sleep_long_tail_s": self.sleep_long_tail_ms / 1e3,
            "accepted_from_stragglers": sum(
                accepted_by_worker[w] for w in self.stragglers) if on else 0,
            "accepted_after_calibration":
                max(0, accepted - self.calibrated_at_update) if on else 0,
            # 0 from a solver that books no age (ASGD, a sync run)
            "history_age_late_sum": self.history_age_late_sum,
            "history_age_late_n": self.history_age_late_n,
            "history_age_healthy_sum": self.history_age_healthy_sum,
            "history_age_healthy_n": self.history_age_healthy_n,
        }
