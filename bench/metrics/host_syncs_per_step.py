"""Device-to-host fetches per decode step in the window, counted by the
program's dispatch tracer."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "engine", "syncs/step", "lower", "program_counter", "itl_p95_ms"


def read(ctx):
    a, b = ctx["counters"]["open"][0], ctx["counters"]["close"][0]
    steps = b.decode_steps - a.decode_steps
    return (b.host_syncs - a.host_syncs) / steps if steps else None
