"""The recurrent-state update's share of its memory roofline: the bytes
a decode dispatch has to move for it (each ready slot's state read once
and written once: twice the program's ``state_bytes``) over the chip's
published HBM bandwidth, against the device time of the ops that touch
the state (``ssm_update_device_ms``). The update is elementwise over the
state: bytes, not FLOPs, bound it."""


def read(ctx):
    from benchmark import flops, ssm_trace
    read_bytes = ssm_trace.state_bytes(ctx)
    if read_bytes is None:
        return None
    got = ssm_trace.update_seconds(ctx)
    if not got:
        return None
    seconds, dispatches = got
    least_s = 2.0 * read_bytes / flops.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / dispatches)
