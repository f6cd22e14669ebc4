"""The full attention layers' read as a share of its memory roofline:
the K and V rows of every position the dispatch's rows attend over (the
program's ``full_rows``) once in each full layer, at the program's own
``kv_row_bytes`` a position (``sink_window_trace.kv_bytes``: 2,560
here), over the chip's published HBM bandwidth, against
``split_kv_full_attn_device_ms``. A walk fetches whole blocks of live
rows and cannot pass 100%."""


def read(ctx):
    from benchmark import sink_window_trace as t
    got = t.counters(ctx)
    if got is None:
        return None
    return t.share_of_peak(ctx, t.kv_bytes(t.sizes(ctx), got, "full"),
                           t.part_ms(ctx, "full"))
