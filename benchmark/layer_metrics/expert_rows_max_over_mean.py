"""How unevenly a decode batch's rows fall on the experts: the fullest
expert's rows (any one layer) over the mean rows an expert received,
from the program's counters in its ``engine_step`` records, over the
traced steps that dispatched a decode batch and no prefill chunk. 1 is
an even spread; a capacity-bound layer would drop what lies above its
factor, this one runs it."""


def read(ctx):
    from benchmark import moe_trace
    got = moe_trace.decode_counters(ctx)
    if got is None:
        return None
    z = moe_trace.sizes(ctx)
    mean = got["expert_rows"] / (z["expert_layers"] * z["experts"])
    return got["expert_rows_max"] / mean
