"""Bytes of recurrent state a step's decode dispatch read: the mean,
over the traced steps, of the ``state_bytes`` the program wrote into its
``engine_step`` span records (one state row of every recurrent layer for
each ready slot; written back the same size). None where the program
writes none."""


def read(ctx):
    from benchmark import ssm_trace
    return ssm_trace.state_bytes(ctx)
