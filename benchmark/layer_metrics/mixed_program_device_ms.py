"""Mean device time of the step programs that the traced steps'
``mixed`` dispatches ran: a full prefill chunk riding with the decode
batch, the program of most steps since PR 36. Paired with its dispatch
by order (``benchmark/dispatch_join.py``): no clock."""


def read(ctx):
    from benchmark import dispatch_join
    return dispatch_join.program_ms(ctx, "mixed")
