"""How unevenly a decode batch's rows fall on the chip's SHARE of the
fine-grained experts: the fullest held expert's rows (any one layer)
over the mean rows a held expert received, from the program's counters
in its ``engine_step`` records, over the traced steps that dispatched a
decode batch and no prefill chunk. 128 rows x 10 choices over 512
experts are 2.5 rows an expert."""


def read(ctx):
    from benchmark import delta_trace as t
    got = t.counters(ctx)
    if got is None or not got.get("expert_rows"):
        return None
    z = t.sizes(ctx)
    mean = got["expert_rows"] / (z["expert_layers"] * z["experts"])
    return got["expert_rows_max"] / mean
