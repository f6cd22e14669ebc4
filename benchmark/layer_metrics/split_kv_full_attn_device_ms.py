"""Device milliseconds a decode-side dispatch spends in the full
attention layers' cache read over K rows of ``4 x 192`` lanes and V
rows of ``4 x 128``: the walk's kernel call a layer (handed the
sequences' whole tables), the query laid out for the stored K rows and
the head pick of its result (``benchmark/sink_window_trace.py``)."""


def read(ctx):
    from benchmark import sink_window_trace
    return sink_window_trace.part_ms(ctx, "full")
