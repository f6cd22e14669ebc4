"""Device milliseconds a decode dispatch spends in the ops that read or
write the recurrent state (``benchmark/ssm_trace.py`` tells them by
their shapes inside the decode program's own events)."""


def read(ctx):
    from benchmark import ssm_trace
    if ssm_trace.state_bytes(ctx) is None:
        return None
    got = ssm_trace.update_seconds(ctx)
    if not got:
        return None
    seconds, dispatches = got
    return 1e3 * seconds / dispatches
