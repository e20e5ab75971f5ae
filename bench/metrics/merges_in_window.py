"""Merges the platform made while the window was open (each one compiles
and stalls the batcher)."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "platform / fusion", "count", "lower", "program_counter", "itl_p95_ms"


def read(ctx):
    return ctx["counters"]["close"][2] - ctx["counters"]["open"][2]
