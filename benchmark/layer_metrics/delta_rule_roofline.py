"""The delta rule's state update as a share of its memory roofline in a
decode dispatch: every launched row's tail and matrix of every
gated-delta layer read once and written once (twice the program's
``state_bytes``: ``delta_trace.delta_rule_bytes``) over the chip's
published HBM bandwidth, against ``delta_rule_device_ms``. Bound by
bytes: a row's update is 6 flops an element of a matrix it has to read
and write."""


def read(ctx):
    from benchmark import delta_trace as t
    got = t.counters(ctx)
    if got is None:
        return None
    return t.share_of_peak(ctx, t.delta_rule_bytes(got["state_bytes"]),
                           t.part_ms(ctx, "delta"))
