"""Mean over the traced steps' dispatches of the host interval from
the ``*.dispatch`` phase's opening to the ``*.readback`` phase's close,
less the device time of the program the dispatch ran: what a launch and
a blocking read cost beyond the program (the launch before it starts,
the return after it ends). A host duration less a device duration,
paired by order (``benchmark/dispatch_join.py``): no clock is compared
with another."""


def read(ctx):
    from benchmark import dispatch_join
    return dispatch_join.overhead_ms(ctx)
