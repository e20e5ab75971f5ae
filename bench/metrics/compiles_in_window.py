"""Backend compiles while the window was open, counted by the program's
dispatch tracer (0 when set-up warmed every program the traffic uses)."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "engine", "count", "lower", "program_counter", "itl_p95_ms"


def read(ctx):
    return ctx["counters"]["close"][0].compiles - ctx["counters"]["open"][0].compiles
