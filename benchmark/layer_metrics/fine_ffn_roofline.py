"""The chip's share of the fine-grained experts as a share of its memory
roofline in a decode dispatch: the bytes of the held experts the
program's counters say received a row, once each, the float32 routers
and the shared experts (``delta_trace.fine_ffn_bytes``) over the chip's
published HBM bandwidth, against ``fine_ffn_device_ms``."""


def read(ctx):
    from benchmark import delta_trace as t
    got = t.counters(ctx)
    if got is None or "experts_touched" not in got:
        return None
    return t.share_of_peak(
        ctx, t.fine_ffn_bytes(t.sizes(ctx), got["experts_touched"]),
        t.part_ms(ctx, "fine"))
