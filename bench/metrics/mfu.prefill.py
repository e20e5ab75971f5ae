"""Model FLOPs of the traced prefill chunks, real prompt tokens only (the
power-of-two padding is waste), over the device time of those chunks'
operations times the bf16 peak."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "model step", "%", "higher", "device_trace", "itl_p95_ms"


def read(ctx):
    import device
    return device.step_mfu(ctx, "prefill")
