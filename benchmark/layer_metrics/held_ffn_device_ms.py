"""Device milliseconds a decode-side dispatch spends in the sparse
layers' ops over the experts HELD here: the router over all published
experts, its choice and weights, every held expert's gate and up
products, the down product over them and the shared expert's stacks
(``benchmark/window_trace.py``)."""


def read(ctx):
    from benchmark import window_trace
    return window_trace.part_ms(ctx, "held")
