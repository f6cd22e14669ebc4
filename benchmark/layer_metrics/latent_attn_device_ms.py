"""Device milliseconds a decode dispatch spends reading the latent
cache: the gather of every slot's rows as stored and the two products
over them, scores and softmax between (``benchmark/moe_trace.py``)."""


def read(ctx):
    from benchmark import moe_trace
    return moe_trace.part_ms(ctx, "latent")
