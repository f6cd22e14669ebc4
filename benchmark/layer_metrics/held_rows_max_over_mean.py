"""How unevenly a decode batch's rows fall on the HELD experts: the
fullest held expert's rows (any one layer) over the mean rows a held
expert received, from the program's counters in its ``engine_step``
records, over the traced steps that dispatched a decode batch and no
prefill chunk. 64 rows x 10 choices over 256 experts are 2.5 rows an
expert; 80 pairs thrown at 32 bins read about 2.4."""


def read(ctx):
    from benchmark import window_trace
    got = window_trace.counters(ctx)
    if got is None or not got.get("expert_rows"):
        return None
    z = window_trace.sizes(ctx)
    mean = got["expert_rows"] / (z["expert_layers"] * z["experts"])
    return got["expert_rows_max"] / mean
