"""The paged decode attention kernel's share of its roofline over the
traced decode steps: the least time the chip needs for the keys and
values each step attended (the configuration's counts module,
``paged_decode_kernel``, summed over the layers) over the device time of
the kernel's events."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "itl_p95_ms"
KERNEL = ("paged_decode_attention",)
COUNT = "paged_decode_kernel"


def read(ctx):
    import device
    return device.kernel_roofline(ctx, "decode", KERNEL, COUNT)
