"""Device milliseconds a decode-side dispatch spends in the expert
layers' ops over the chip's SHARE of the fine-grained experts: the
softmax router over all published experts, the choice and its weights,
every held expert's gate and up products and the down product over
them, and the shared expert's three products
(``benchmark/delta_trace.py``)."""


def read(ctx):
    from benchmark import delta_trace
    return delta_trace.part_ms(ctx, "fine")
