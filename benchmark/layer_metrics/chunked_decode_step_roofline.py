"""The decode-side program's share of its memory roofline for the
chunk-summarised attention model: every decode leaf once
(``decode_weight_bytes``) and both stores' attended rows once
(``chunk_trace.decode_step_bytes``) over the chip's published HBM
bandwidth, against the device time of one decode-side program, found by
the traced records' ``dispatches`` by ordinal (``chunk_trace.decode_ms``):
the share of the whole step."""


def read(ctx):
    from benchmark import chunk_trace, flops
    got = chunk_trace.counters(ctx)
    ms = chunk_trace.decode_ms(ctx)
    if got is None or not ms:
        return None
    need = chunk_trace.decode_step_bytes(
        chunk_trace.sizes(ctx), ctx["values"]["weight_bytes"], got)
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
