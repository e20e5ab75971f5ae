"""Live instances serving the chain when the window closes: 1 when the
platform fused the whole chain, more when it left edges unfused."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "platform / fusion", "count", "lower", "program_counter", "itl_p95_ms"


def read(ctx):
    return ctx["counters"]["close"][1]
