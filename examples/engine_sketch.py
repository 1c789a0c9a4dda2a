"""A discrete-event sketch of the engine's loop: what rate, idle share and
occupancy follow from a step time and the medians of the host's stages.

    python examples/engine_sketch.py --workers 8 --chips 4 --step-ms 4.2414 \\
        --inbox-ms 1.9 --dispatch-ms 1.4 --notice-ms 0.3

Pure Python, no JAX, nothing of the program is imported: it is a SKETCH, a
way to ask "what would the cell read if this stage were a tenth of its
size" before anybody writes the change, not a model anybody should trust
to a per cent.  What it holds: ``workers`` workers dealt round-robin over
``chips`` chips, each chip ONE queue that runs steps first in, first out;
the recipe's partial barrier (a cohort is every available worker, and goes
out only while at least ``floor(workers * bucket_ratio)`` are available;
workers never seen go at once); a submitter that is busy ``submit_ms`` a
cohort and sleeps ``poll_ms`` after a poll that sent nothing; and for each
task the stages of ``metrics/trace.py`` as fixed times with a seeded
jitter:

    submit --inbox_ms--> closure entered --dispatch_ms--> step enqueued
    --launch_ms--> (the chip's queue) the step, step_ms --notice_ms-->
    block_until_ready returned --result_ms--> the worker available again

``inbox_ms`` is ``task.inbox`` (the submitter's work in front of the put
and ``task.wake``), ``dispatch_ms`` is ``task.dispatch`` (``task.turn`` +
``task.model_copy``, which is nothing where the model lives on every chip,
+ ``task.enqueue`` + its own time), ``launch_ms`` +
``notice_ms`` is what ``task.device_wait.alone`` reads over the step's
device time (``empty_chip_wait_excess_ms``).

What it leaves out: the interpreter lock (eight executor threads and the
two serial ones share ONE: here every task's host path runs beside the
others'), the updater (a result makes its worker available the moment it
is queued, as in the program, and nothing here applies it) and with it
every lock the two serial threads share (PR 47: the submitter stood
behind the updater's dispatches under the state lock, which no stage's
median shows as such; the sketch priced the model on every chip at +5.7%
and the chip read -4.5% with that lock held and +14% without), the
backlog bound, host stalls, steps of unequal length.  It reads the same account
the program keeps (``instrumentation.Occupancy``): a task is in flight
from its submit to its result, a chip is empty while none of its workers
is.
"""

from __future__ import annotations

import argparse
import heapq
import itertools
import json
import math
import random


def simulate(workers: int = 8, chips: int = 4, bucket_ratio: float = 0.7,
             step_ms: float = 4.2414, inbox_ms: float = 1.9,
             dispatch_ms: float = 1.4, launch_ms: float = 0.0,
             notice_ms: float = 0.3, result_ms: float = 0.0,
             submit_ms: float = 0.3, poll_ms: float = 1.0,
             seconds: float = 20.0, jitter: float = 0.2,
             seed: int = 0) -> dict:
    """One run of ``seconds``.  Every host stage of every task is drawn
    uniformly within ``jitter`` of its median (the device's step is not:
    it repeats to a per cent on the chip).  Returns ``updates_per_s``,
    ``device_idle`` and ``chip_starved`` (per cent), ``inflight_mean``,
    ``barrier_hold`` (per cent of the run the submitter slept with workers
    available and the bucket holding them) and ``cohort_mean``."""
    if workers < 1 or chips < 1 or step_ms <= 0:
        raise ValueError("workers, chips and step_ms must be positive")
    rng = random.Random(seed)

    def drawn(median_ms: float) -> float:
        return median_ms * (1.0 + jitter * (2.0 * rng.random() - 1.0))

    horizon = seconds * 1e3
    threshold = math.floor(workers * bucket_ratio)
    chip_of = [wid % chips for wid in range(workers)]
    available = set(range(workers))
    seen = set()                       # workers that have had a task
    chip_free = [0.0] * chips          # when the chip's queue runs empty
    chip_busy = [0.0] * chips          # step time inside the horizon
    on_chip = [0] * chips              # tasks between submit and result
    empty_since = [0.0] * chips
    chip_empty = [0.0] * chips
    inflight = 0
    inflight_area = 0.0
    mark = 0.0
    done = cohorts = submitted = 0
    held_ms = 0.0
    events = []
    order = itertools.count()  # ties go first pushed, first popped

    def push(at: float, kind: str, wid: int = -1) -> None:
        heapq.heappush(events, (at, next(order), kind, wid))

    push(0.0, "poll")
    while events:
        now, _, kind, wid = heapq.heappop(events)
        if now >= horizon:
            break
        inflight_area += inflight * (now - mark)
        mark = now
        if kind == "poll":
            cold = [w for w in sorted(available) if w not in seen]
            cohort = (sorted(available) if len(available) >= threshold
                      else cold)
            if not cohort:
                if available:
                    held_ms += poll_ms
                push(now + poll_ms, "poll")
                continue
            cohorts += 1
            submitted += len(cohort)
            for w in cohort:
                available.discard(w)
                seen.add(w)
                chip = chip_of[w]
                if on_chip[chip] == 0:
                    chip_empty[chip] += now - empty_since[chip]
                on_chip[chip] += 1
                inflight += 1
                push(now + drawn(inbox_ms) + drawn(dispatch_ms),
                     "enqueue", w)
            push(now + drawn(submit_ms), "poll")
        elif kind == "enqueue":
            chip = chip_of[wid]
            start = max(now + drawn(launch_ms), chip_free[chip])
            end = start + step_ms
            chip_free[chip] = end
            chip_busy[chip] += max(0.0, min(end, horizon) - min(start, horizon))
            push(end + drawn(notice_ms) + drawn(result_ms), "result", wid)
        else:  # the worker's result: it is available again
            chip = chip_of[wid]
            on_chip[chip] -= 1
            if on_chip[chip] == 0:
                empty_since[chip] = now
            inflight -= 1
            available.add(wid)
            done += 1
    inflight_area += inflight * (horizon - mark)
    for chip in range(chips):
        if on_chip[chip] == 0:
            chip_empty[chip] += horizon - empty_since[chip]
    return {
        "updates_per_s": done / seconds,
        "device_idle": 100.0 * (1.0 - sum(chip_busy) / (chips * horizon)),
        "chip_starved": 100.0 * max(chip_empty) / horizon,
        "inflight_mean": inflight_area / horizon,
        "barrier_hold": 100.0 * held_ms / horizon,
        "cohort_mean": submitted / max(1, cohorts),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--bucket-ratio", type=float, default=0.7)
    ap.add_argument("--step-ms", type=float, default=4.2414)
    for stage, default in (("inbox", 1.9), ("dispatch", 1.4), ("launch", 0.0),
                           ("notice", 0.3), ("result", 0.0), ("submit", 0.3),
                           ("poll", 1.0)):
        ap.add_argument(f"--{stage}-ms", type=float, default=default)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--jitter", type=float, default=0.2)
    ap.add_argument("--seeds", type=int, default=5,
                    help="runs, seeds 0..n-1: the line gives each figure's "
                    "lowest and highest")
    args = ap.parse_args(argv)
    kw = {k: v for k, v in vars(args).items() if k != "seeds"}
    runs = [simulate(seed=seed, **kw) for seed in range(args.seeds)]
    print(json.dumps({
        name: [min(r[name] for r in runs), max(r[name] for r in runs)]
        for name in runs[0]
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
