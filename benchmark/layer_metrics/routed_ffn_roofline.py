"""The routed experts' share of their memory roofline in a decode
dispatch: the bytes of the experts the program's counters say received
a row and the routers (``conv_moe_trace.routed_ffn_bytes``) over the
chip's published HBM bandwidth, against ``routed_ffn_device_ms``. At 64
rows the layer is bound by bytes: every held expert over every row is 77
GFLOP a layer, 0.4 ms of the MXU against 1.5 ms of weights."""


def read(ctx):
    from benchmark import conv_moe_trace, flops
    got = conv_moe_trace.counters(ctx)
    ms = conv_moe_trace.part_ms(ctx, "routed")
    if got is None or not ms:
        return None
    need = conv_moe_trace.routed_ffn_bytes(conv_moe_trace.sizes(ctx),
                                           got["experts_touched"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
