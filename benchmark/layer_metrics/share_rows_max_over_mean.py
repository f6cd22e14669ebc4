"""How unevenly a decode batch's rows fall on the chip's SHARE of the
experts: the fullest held expert's rows (any one layer) over the mean
rows a held expert received, from the program's counters in its
``engine_step`` records, over the traced steps that dispatched a decode
batch and no prefill chunk. 64 rows x 8 choices over 256 experts are 2
rows an expert; 32 pairs thrown at 16 bins read about 2.5."""


def read(ctx):
    from benchmark import sink_window_trace as t
    got = t.counters(ctx)
    if got is None or not got.get("expert_rows"):
        return None
    z = t.sizes(ctx)
    mean = got["expert_rows"] / (z["expert_layers"] * z["experts"])
    return got["expert_rows_max"] / mean
