"""Share of the traced window in which no operation ran on the device."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "device", "%", "lower", "device_trace", "itl_p95_ms"


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
