"""Device milliseconds a decode-side program spends on the chunked
layers' SUMMARIES: their read (the walk over each row's summary rows),
the join of the two reads under one softmax, and the summarise-and-write
of the chunks the step's rows finished (``benchmark/chunk_trace.py``)."""


def read(ctx):
    from benchmark import chunk_trace
    return chunk_trace.part_ms(ctx, "summary")
