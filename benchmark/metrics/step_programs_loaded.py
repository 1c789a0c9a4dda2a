"""The share of a padded-ELL solver's worker-step executables that were
LOADED from the store beside the compile cache, of those it loaded or
built (``step_programs_loaded`` over it plus ``step_programs_built`` of
``TrainResult.extras``, counted over the solver object's life: the shapes
come in its warm-up run, and the window's run reports the same table).
100 on a machine that has run the cell before: no step was traced or
lowered in this process; 0 on a machine's first run, which builds and
stores every shape (and counts a stored file that did not load among the
built).  None where the program keeps no such store (every tree before
PR 57) or the solver's steps stay on ``jit`` (the dense cells)."""

NAME = "step_programs_loaded"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "set-up"
MOVES = "setup_s"


def read(run, trace):
    extras = run["result"]["extras"]
    loaded = extras.get("step_programs_loaded")
    built = extras.get("step_programs_built")
    if loaded is None or built is None or loaded + built == 0:
        return None
    return 100.0 * loaded / (loaded + built)
