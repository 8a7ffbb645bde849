"""Python tracing plus lowering to StableHLO of every program the process
built before the window, from the program's build account
(`compile_watcher().stage_seconds(until=run.t0)`, stages `trace` and
`lower`): the part of set-up that no compile cache saves, and that grows with
every layer traced again."""

from benchmark import readings_build

NAME, UNIT, BETTER = "build_trace_lower_s", "s", "lower"
LAYER, MOVES, SOURCE = "Program build", "setup_s", "program_counter"


def read(run):
    stages = readings_build.stage_seconds(run)
    if stages is None:
        return None
    return stages.get("trace", 0.0) + stages.get("lower", 0.0)
