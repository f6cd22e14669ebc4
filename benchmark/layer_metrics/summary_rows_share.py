"""Of the cached entries the traced steps' launched rows attend over,
the share in percent that are chunk summaries and not exact positions
of the row's own window: the program's ``summary_rows`` over
``summary_rows + window_rows`` (``chunk_trace.rows_share_pct``)."""


def read(ctx):
    from benchmark import chunk_trace
    return chunk_trace.rows_share_pct(ctx)
