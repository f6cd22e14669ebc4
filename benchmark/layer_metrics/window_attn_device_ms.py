"""Device milliseconds a decode-side dispatch spends in the window
layers' cache read: the gather of every row's ring, the scores and the
weighted sum over the gathered rows (``benchmark/window_trace.py``
tells them by the shapes of their results and operands inside the
decode-side programs' own events)."""


def read(ctx):
    from benchmark import window_trace
    return window_trace.part_ms(ctx, "window")
