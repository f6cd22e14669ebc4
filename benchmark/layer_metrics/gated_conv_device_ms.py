"""Device milliseconds a decode dispatch spends in the gated short
convolutions: the ``W_in`` product, the ``B * X`` product, the kernel
call that advances the stored tails (``ops/ssm.py::
conv_step_in_place``) with the compiler's staging of the store, and the
``C * v`` / ``W_out`` fusion (``benchmark/conv_moe_trace.py`` tells them
by the shapes of their results and operands inside the decode program's
own events)."""


def read(ctx):
    from benchmark import conv_moe_trace
    return conv_moe_trace.part_ms(ctx, "conv")
