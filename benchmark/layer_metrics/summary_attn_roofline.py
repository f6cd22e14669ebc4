"""The summaries' read, join and write as a share of their memory
roofline: every attended summary's K and V row once in each layer (the
program's ``summary_rows``) and, for each chunk the step's rows
finished (``summaries_written``), its block read and its one row
written (``chunk_trace.summary_bytes``) over the chip's published HBM
bandwidth, against ``summary_attn_device_ms``."""


def read(ctx):
    from benchmark import chunk_trace, flops
    got = chunk_trace.counters(ctx)
    ms = chunk_trace.part_ms(ctx, "summary")
    if got is None or not ms:
        return None
    need = chunk_trace.summary_bytes(chunk_trace.sizes(ctx), got)
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
