"""The paged chunked-prefill attention kernel's share of its roofline
over the traced prefill chunks: the least time for the causal attention
of each chunk's real rows (the configuration's counts module,
``paged_chunk_kernel``, summed over the layers) over the device time of
the kernel's events."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "itl_p95_ms"
KERNEL = ("paged_chunk_attention",)
COUNT = "paged_chunk_kernel"


def read(ctx):
    import device
    return device.kernel_roofline(ctx, "prefill", KERNEL, COUNT)
