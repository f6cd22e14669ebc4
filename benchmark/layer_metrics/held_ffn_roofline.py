"""The held experts' share of their memory roofline in a decode
dispatch: the bytes of the held experts the program's counters say
received a row, the routers and the shared experts
(``window_trace.held_ffn_bytes``) over the chip's published HBM
bandwidth, against ``held_ffn_device_ms``."""


def read(ctx):
    from benchmark import flops, window_trace
    got = window_trace.counters(ctx)
    ms = window_trace.part_ms(ctx, "held")
    if got is None or not ms or "experts_touched" not in got:
        return None
    need = window_trace.held_ffn_bytes(window_trace.sizes(ctx),
                                       got["experts_touched"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
