"""Device milliseconds a decode-side dispatch spends in the expert
layers' ops over the chip's SHARE of the experts: the sigmoid router
over all published experts with its choice bias, the choice and its
weights, every held expert's gate and up products and the down product
over them (``benchmark/sink_window_trace.py``); no shared expert."""


def read(ctx):
    from benchmark import sink_window_trace
    return sink_window_trace.part_ms(ctx, "share")
