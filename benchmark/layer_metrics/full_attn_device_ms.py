"""Device milliseconds a decode-side dispatch spends in the full
attention layers' cache read: the gather of every row's whole table at
capacity, the scores and the weighted sum over the gathered rows
(``benchmark/window_trace.py``)."""


def read(ctx):
    from benchmark import window_trace
    return window_trace.part_ms(ctx, "full")
