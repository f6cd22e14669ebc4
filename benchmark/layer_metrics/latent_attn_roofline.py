"""The latent-cache read's share of its memory roofline in a decode
dispatch: the live rows once (stored bytes a token from the pool's own
arrays x the cached positions the dispatch read) over the chip's
published HBM bandwidth, against ``latent_attn_device_ms``. The gather
follows the slots' CAPACITY, not what is live, so the share reads low by
design until the read does."""


def read(ctx):
    from benchmark import flops, moe_trace
    v = ctx["values"]
    ms = moe_trace.part_ms(ctx, "latent")
    if not ms or not v.get("traced_mean_live_tokens"):
        return None
    need = v["kv_bytes_per_token"] * v["traced_mean_live_tokens"]
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
