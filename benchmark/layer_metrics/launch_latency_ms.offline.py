"""Mean over the traced steps' dispatches of the time from the
``*.dispatch`` phase's opening to the start of the program it launched,
the device plane moved by ``device_plane_lead_ms``: the launch side of
``dispatch_overhead_ms.offline`` (the return side is that less this).
Known to half the width of the feasible leads, which
``benchmark/dispatch_join.py`` prints in its stderr note."""


def read(ctx):
    from benchmark import dispatch_join
    return dispatch_join.launch_latency_ms(ctx)
