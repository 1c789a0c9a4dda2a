"""What an update's task and result handler stood at the engine's locks,
in milliseconds an update: ``lock_wait_executor_s`` of
``TrainResult.extras`` (every contended wait of every executor thread: the
handler at ``key_lock`` and at the context's lock in ``merge_result``; under
ASAGA the slices' lock) over the results that came back (accepted +
dropped).  Over EVERY update, not the sampled ones.  It lies in
``compute``'s self time (``task_p50_ms`` less its children), which no stage
split.  0.0 where nothing waited; None where the program keeps no such
clock (before ISSUE 53) or nothing came back."""

NAME = "task_lock_wait_ms"
UNIT = "ms"
SOURCE = "program_counter"
LAYER = "engine"
MOVES = "updates_per_s"
COUNTER = "lock_wait_executor_s"


def read(run, trace):
    result = run["result"]
    waited = result["extras"].get(COUNTER)
    attempted = result["accepted"] + result["dropped"]
    if waited is None or not attempted:
        return None
    return 1e3 * waited / attempted
