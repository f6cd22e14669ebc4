"""The window layers' read as a share of its memory roofline: the K and
V rows of the positions the dispatch's rows attend over (at most the
window a row, the program's ``window_rows``) once in each window layer,
at the program's own ``window_row_bytes`` a position
(``sink_window_trace.kv_bytes``: 5,120 here), over the chip's published
HBM bandwidth, against ``sink_window_attn_device_ms``. What ANY
implementation must move; the sink moves nothing."""


def read(ctx):
    from benchmark import sink_window_trace as t
    got = t.counters(ctx)
    if got is None:
        return None
    return t.share_of_peak(ctx, t.kv_bytes(t.sizes(ctx), got, "window"),
                           t.part_ms(ctx, "window"))
