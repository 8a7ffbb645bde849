"""Programs built before the window that the persistent compile cache did
not hold, so that they were compiled and written to it
(`compile_watcher().cache_results(until=run.t0)`, result `miss`).  0 in a
warm run; where one side of a pair reads more, that side ran cold, and its
`setup_s` says nothing of the program."""

from benchmark import readings_build

NAME, UNIT, BETTER = "build_cache_misses", "programs", "lower"
LAYER, MOVES, SOURCE = "Program build", "setup_s", "program_counter"


def read(run):
    return readings_build.cache_misses(run)
