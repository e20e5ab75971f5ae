"""95th percentile of the batcher's prefill stall: the program's
``prefill-stall`` spans (pages allocated -> first token) of the requests
that arrived in the window."""
LAYER, UNIT, BETTER, SOURCE, MOVES = "batcher", "ms", "lower", "program_span", "itl_p95_ms"


def read(ctx):
    import spans
    return spans.p95_ms(ctx["spans"], "prefill-stall")
