"""The decode-side program's share of its memory roofline for the gated
delta-rule, fine-grained expert model: the touched held experts, every
other leaf of ``decode_weight_bytes`` once, the launched rows' state
read and written and the full layers' live K/V blocks once
(``delta_trace.decode_step_bytes``) over the chip's published HBM
bandwidth, against the device time of one decode-side dispatch, found by
the ordinal of its launch: the share of the whole step."""


def read(ctx):
    from benchmark import delta_trace as t
    got = t.counters(ctx)
    if got is None or "experts_touched" not in got:
        return None
    return t.share_of_peak(
        ctx, t.decode_step_bytes(t.sizes(ctx), ctx["values"]["weight_bytes"],
                                 got), t.decode_ms(ctx))
