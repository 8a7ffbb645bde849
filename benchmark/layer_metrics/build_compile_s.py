"""The `backend` stage of every program the process built before the window,
from the program's build account
(`compile_watcher().stage_seconds(until=run.t0)`): XLA compiling, or the
load from the persistent compile cache where it held the program.  Read it
beside `build_cache_misses`: a cold run compiles, a warm one loads."""

from benchmark import readings_build

NAME, UNIT, BETTER = "build_compile_s", "s", "lower"
LAYER, MOVES, SOURCE = "Program build", "setup_s", "program_counter"


def read(run):
    stages = readings_build.stage_seconds(run)
    if stages is None:
        return None
    return stages.get("backend", 0.0)
