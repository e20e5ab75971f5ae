"""Pallas TPU kernels for the platform's compute hot spots.

flash_attention / decode_attention / paged_attention / ssd_scan / moe_gmm,
each with a pure-jnp oracle in ref.py and a jit'd dispatcher in ops.py
(kernel on TPU, oracle on CPU, interpret mode for validation).
paged_attention reads the serving subsystem's shared KV arena through
scalar-prefetched block tables: its decode kernel runs one grid step per
batch slot and DMAs only the slot's live pages, several per block; its
off-TPU fallback is one XLA gather + the dense oracle.
"""
