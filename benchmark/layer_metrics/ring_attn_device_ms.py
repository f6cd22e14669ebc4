"""Device milliseconds a decode-side program spends in the chunked
layers' RING read: the gather of every row's ring, the scores and the
weighted sum over the gathered rows, or a kernel call with that result
(``benchmark/chunk_trace.py`` finds the decode-side programs by the
traced records' ``dispatches``, by ordinal, and tells the ops by the
shapes of their results and operands)."""


def read(ctx):
    from benchmark import chunk_trace
    return chunk_trace.part_ms(ctx, "ring")
