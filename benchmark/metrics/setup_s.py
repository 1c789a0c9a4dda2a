"""Process start to the call of the solver's ``run()``: chip attach,
imports, data generation, compile or cache load, and the fenced warm-up."""

NAME = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(run, trace):
    return run["spans"]["setup_s"]
