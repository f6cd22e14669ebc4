"""The full attention layers' read as a share of its memory roofline:
the K and V rows of every position the dispatch's rows attend over (the
program's ``full_rows``) once in each full layer
(``window_trace.kv_bytes``) over the chip's published HBM bandwidth,
against ``full_attn_device_ms``. The gather reads each table at
capacity: the share is the live part of it at best."""


def read(ctx):
    from benchmark import flops, window_trace
    got = window_trace.counters(ctx)
    ms = window_trace.part_ms(ctx, "full")
    if got is None or not ms:
        return None
    need = window_trace.kv_bytes(window_trace.sizes(ctx), "full",
                                 got["full_rows"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
