"""Device milliseconds a decode dispatch spends in the routed experts'
ops: the router, its sort and weights, every held expert's gate and up
products and the down product over them (``benchmark/conv_moe_trace.py``
tells them by the shapes of their results and operands inside the decode
program's own events, and the decode program by its own ops)."""


def read(ctx):
    from benchmark import conv_moe_trace
    return conv_moe_trace.part_ms(ctx, "routed")
