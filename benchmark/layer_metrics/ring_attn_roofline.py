"""The ring's read as a share of its memory roofline: the K and V rows
of the positions of the launched rows' own aligned windows (the
program's ``window_rows``) once in each layer
(``chunk_trace.ring_bytes``) over the chip's published HBM bandwidth,
against ``ring_attn_device_ms``. What ANY implementation must move: a
read that fetches less than a ring cannot pass 100%."""


def read(ctx):
    from benchmark import chunk_trace, flops
    got = chunk_trace.counters(ctx)
    ms = chunk_trace.part_ms(ctx, "ring")
    if got is None or not ms:
        return None
    need = chunk_trace.ring_bytes(chunk_trace.sizes(ctx), got)
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
