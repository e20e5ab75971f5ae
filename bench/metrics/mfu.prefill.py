"""Model FLOPs of the traced prefill chunks, real prompt tokens only (the
power-of-two padding is waste; the configuration's counts module,
``prefill_chunk_flops``), over the device time of those chunks' operations
times the bf16 peak."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "model step", "%", "higher", "device_trace", "itl_p95_ms"
COUNT = "prefill_chunk_flops"


def read(ctx):
    import device
    return device.step_mfu(ctx, "prefill", COUNT)
