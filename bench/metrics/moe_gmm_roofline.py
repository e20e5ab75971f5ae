"""The grouped-matmul expert kernel's share of its roofline over the traced
decode steps: the least time the chip needs for each step's three expert
matmuls of every layer (the configuration's counts module,
``moe_gmm_kernel``: the routed rows' FLOPs, and bytes for the rows and the
weights of the experts the step's tokens touch) over the device time of the
kernel's events. None where no op carries the kernel's name."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "itl_p95_ms"
KERNEL = ("moe_gmm",)
COUNT = "moe_gmm_kernel"


def read(ctx):
    import device
    return device.kernel_roofline(ctx, "decode", KERNEL, COUNT)
