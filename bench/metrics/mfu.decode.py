"""Model FLOPs of the traced decode steps (``bench/flops.py``) over the
device time of those steps' operations times the bf16 peak."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "model step", "%", "higher", "device_trace", "itl_p95_ms"


def read(ctx):
    import device
    return device.step_mfu(ctx, "decode")
