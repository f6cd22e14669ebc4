"""The decode-side program's share of its memory roofline for the
window-and-full-attention expert model: the touched held experts, every
other leaf of ``decode_weight_bytes`` once and both kinds' attended K/V
rows once (``window_trace.decode_step_bytes``) over the chip's published
HBM bandwidth, against the device time of one decode-side dispatch, told
from the prefill chunk by its own ops (``window_trace.decode_ms``): the
share of the whole step."""


def read(ctx):
    from benchmark import flops, window_trace
    got = window_trace.counters(ctx)
    ms = window_trace.decode_ms(ctx)
    if got is None or not ms or "experts_touched" not in got:
        return None
    need = window_trace.decode_step_bytes(
        window_trace.sizes(ctx), ctx["values"]["weight_bytes"], got)
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
