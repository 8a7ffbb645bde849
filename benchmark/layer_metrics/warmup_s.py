"""What `warmup()` cost: `lm.stats()["warmup"]["total_s"]`, every program of
the server compiled or loaded and run once, each waited for (the programs'
own seconds are printed beside it by key in `stats()`)."""

NAME, UNIT, BETTER = "warmup_s", "s", "lower"
LAYER, MOVES, SOURCE = "Model step programs", "setup_s", "program_counter"


def read(run):
    return run.counters.get("after", {}).get("warmup", {}).get("total_s")
