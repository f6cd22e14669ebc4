"""Mean device time of the step programs that the traced steps'
``decode`` dispatches ran: the program over the ready batch alone, on
the steps with no chunk to carry and behind a tail chunk's. Paired with
its dispatch by order (``benchmark/dispatch_join.py``): no clock, so it
reads the same whatever the profiler's device plane leads its host
plane by."""


def read(ctx):
    from benchmark import dispatch_join
    return dispatch_join.program_ms(ctx, "decode")
