"""By how much the profiler's device plane leads its host plane in this
trace (positive: device stamps are early): the midpoint of the leads
under which every paired program lies between its dispatch phase's
opening and its readback's close (``benchmark/dispatch_join.py``, whose
stderr note gives the interval's ends). Over about 0.3 ms, the readers
that put a program in a step by comparing the two planes' stamps
(``engine_trace.py``, ``ssm_trace.py``) name the programs of this trace
for each other."""


def read(ctx):
    from benchmark import dispatch_join
    return dispatch_join.lead_ms(ctx)
