"""The window layers' read as a share of its memory roofline: the K and
V rows of the positions the dispatch's rows attend over (at most the
window a row, the program's ``window_rows``) once in each window layer
(``window_trace.kv_bytes``) over the chip's published HBM bandwidth,
against ``window_attn_device_ms``. What ANY implementation must move:
a read that gathers less than a ring cannot pass 100%."""


def read(ctx):
    from benchmark import flops, window_trace
    got = window_trace.counters(ctx)
    ms = window_trace.part_ms(ctx, "window")
    if got is None or not ms:
        return None
    need = window_trace.kv_bytes(window_trace.sizes(ctx), "window",
                                 got["window_rows"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
