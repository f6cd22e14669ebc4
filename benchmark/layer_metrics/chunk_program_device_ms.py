"""Mean device time of the step programs that the traced steps'
``prefill`` dispatches ran: a chunk in a program of its own, which since
PR 36 is a prompt's tail (every chunk bucket in one mean). Paired with
its dispatch by order (``benchmark/dispatch_join.py``): no clock."""


def read(ctx):
    from benchmark import dispatch_join
    return dispatch_join.program_ms(ctx, "prefill")
