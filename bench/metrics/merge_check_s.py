"""Seconds the platform spent in merge health checks before the window
opened: the summed durations of its ``merge.check`` phases (replays of the
canaries on the old path and on the fused unit, with its compile, and the
comparison), which the program's dispatch tracer adds up. None from a
program that keeps no such sum."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "platform / fusion", "s", "lower", "program_span", "setup_s"


def read(ctx):
    return getattr(ctx["counters"]["open"][0], "merge_check_s", None)
