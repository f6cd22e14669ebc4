"""An expert model's decode program's share of its memory roofline: the
experts the counters say were touched, every other leaf of
``decode_weight_bytes`` once and the live latent rows once
(``moe_trace.moe_decode_step_bytes``) over the chip's published HBM
bandwidth, against the device time of one decode dispatch, the decode
program told from the prefill chunk by its own ops
(``moe_trace.decode_ms``). ``decode_step_roofline`` counts every weight
as read and stays with the models that read them all."""


def read(ctx):
    from benchmark import flops, moe_trace
    v = ctx["values"]
    got = moe_trace.decode_counters(ctx)
    ms = moe_trace.decode_ms(ctx)
    if got is None or not ms or not v.get("traced_mean_live_tokens"):
        return None
    need = moe_trace.moe_decode_step_bytes(
        moe_trace.sizes(ctx), v["weight_bytes"], got["experts_touched"],
        v["kv_bytes_per_token"], v["traced_mean_live_tokens"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
