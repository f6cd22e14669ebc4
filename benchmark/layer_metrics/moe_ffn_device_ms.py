"""Device milliseconds a decode dispatch spends in the expert layers'
ops: the router, every held expert's gate and up products and the down
product over them, the shared expert's gate and up
(``benchmark/moe_trace.py`` tells them by their shapes inside the decode
program's own events, and the decode program by its own ops)."""


def read(ctx):
    from benchmark import moe_trace
    return moe_trace.part_ms(ctx, "moe")
