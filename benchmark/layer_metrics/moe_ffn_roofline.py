"""The expert layers' share of their memory roofline in a decode
dispatch: the bytes of the experts the program's counters say received
a row, the routers and the shared experts' gate and up
(``moe_trace.moe_ffn_bytes``) over the chip's published HBM bandwidth,
against ``moe_ffn_device_ms``. At 64 rows the layer is bound by bytes:
every held expert over every row is 77 GFLOP a layer, 0.4 ms of the MXU
against 1.5 ms of weights."""


def read(ctx):
    from benchmark import flops, moe_trace
    got = moe_trace.decode_counters(ctx)
    ms = moe_trace.part_ms(ctx, "moe")
    if got is None or not ms:
        return None
    need = moe_trace.moe_ffn_bytes(moe_trace.sizes(ctx),
                                   got["experts_touched"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
