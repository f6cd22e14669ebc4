"""Device milliseconds a decode-side dispatch spends updating the
recurrent state of the gated delta-rule layers where it is stored: a
layer's convolution kernel and its delta kernel (each a ``custom-call``
handed and handing back its store whole) and the ops between them that
touch the state, the three rows and the key and query columns a batch
row brings to the delta kernel (``benchmark/delta_trace.py`` tells them
by the stores' shapes and the row's two widths, inside the decode-side
programs' own events, and those programs by the ordinal of their
launches)."""


def read(ctx):
    from benchmark import delta_trace
    return delta_trace.part_ms(ctx, "delta")
