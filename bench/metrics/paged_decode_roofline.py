"""The paged decode attention kernel's share of its roofline over the
traced decode steps: the least time the chip needs for the keys and
values each step attended (``bench/flops.py``, every layer) over the
device time of the kernel's events."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "itl_p95_ms"
KERNEL = ("paged_decode_attention",)


def read(ctx):
    import device
    return device.kernel_roofline(ctx, "decode", KERNEL)
