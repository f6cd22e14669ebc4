"""How unevenly a decode batch's rows fall on the routed experts: the
fullest expert's rows (any one layer) over the mean rows an expert
received, from the program's counters in its ``engine_step`` records,
over the traced steps that dispatched a decode batch and no prefill
chunk. 1 is an even spread; 256 pairs thrown evenly at random over 64
experts read about 2.5 (the fullest of 64 bins of mean 4)."""


def read(ctx):
    from benchmark import conv_moe_trace
    got = conv_moe_trace.counters(ctx)
    if got is None:
        return None
    z = conv_moe_trace.sizes(ctx)
    mean = got["expert_rows"] / (z["expert_layers"] * z["experts"])
    return got["expert_rows_max"] / mean
