"""The decode program's share of its memory roofline for the gated
convolution, sparse-expert model: the experts the counters say were
touched, every other leaf of ``decode_weight_bytes`` once, the live K/V
rows once and the ready rows' tails read and written
(``conv_moe_trace.decode_step_bytes``) over the chip's published HBM
bandwidth, against the device time of one decode dispatch, the decode
program told from the prefill chunk by its own ops
(``conv_moe_trace.decode_ms``)."""


def read(ctx):
    from benchmark import conv_moe_trace, flops
    v = ctx["values"]
    got = conv_moe_trace.counters(ctx)
    ms = conv_moe_trace.decode_ms(ctx)
    if got is None or not ms or not v.get("traced_mean_live_tokens"):
        return None
    need = conv_moe_trace.decode_step_bytes(
        conv_moe_trace.sizes(ctx), v["weight_bytes"], got["experts_touched"],
        v["kv_bytes_per_token"], v["traced_mean_live_tokens"],
        got["state_bytes"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
