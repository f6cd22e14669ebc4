"""The gated short convolutions' share of their memory roofline in a
decode dispatch: the ready rows' tails read and written and the
convolution mixers' weights once (``conv_moe_trace.gated_conv_bytes``)
over the chip's published HBM bandwidth, against
``gated_conv_device_ms``. Bound by bytes: a row's mixer is 34 MFLOP
against 34 MB of weights a layer."""


def read(ctx):
    from benchmark import conv_moe_trace, flops
    got = conv_moe_trace.counters(ctx)
    ms = conv_moe_trace.part_ms(ctx, "conv")
    if got is None or not ms:
        return None
    need = conv_moe_trace.gated_conv_bytes(conv_moe_trace.sizes(ctx),
                                           got["state_bytes"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
