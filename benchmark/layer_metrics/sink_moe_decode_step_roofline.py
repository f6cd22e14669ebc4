"""The decode-side program's share of its memory roofline for the
sink-softmax, split-width, expert-share model: the touched held
experts, every other leaf of ``decode_weight_bytes`` once and both
stores' attended rows once at the program's own bytes a position
(``sink_window_trace.decode_step_bytes``) over the chip's published HBM
bandwidth, against the device time of one decode-side dispatch, found
by the ordinal of its launch: the share of the whole step."""


def read(ctx):
    from benchmark import sink_window_trace as t
    got = t.counters(ctx)
    if got is None or "experts_touched" not in got:
        return None
    return t.share_of_peak(
        ctx, t.decode_step_bytes(t.sizes(ctx), ctx["values"]["weight_bytes"],
                                 got), t.decode_ms(ctx))
