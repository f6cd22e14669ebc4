"""Device milliseconds a decode-side dispatch spends in the window
layers' cache read, whose softmax has a sink and whose K and V rows
differ in width: the walk's kernel call a layer (a ``custom-call`` with
the result ``f32[b, H, V row]`` that is handed the rings as tables and
the sinks), the query laid out for the stored K rows and the head pick
of the result (``benchmark/sink_window_trace.py`` tells them by the
store's two row widths and by the tables, inside the decode-side
programs' own events, and those programs by the ordinal of their
launches)."""


def read(ctx):
    from benchmark import sink_window_trace
    return sink_window_trace.part_ms(ctx, "window")
