"""Model FLOPs of the traced decode steps (the configuration's counts
module, ``decode_step_flops``) over the device time of those steps'
operations times the bf16 peak."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "model step", "%", "higher", "device_trace", "itl_p95_ms"
COUNT = "decode_step_flops"


def read(ctx):
    import device
    return device.step_mfu(ctx, "decode", COUNT)
