"""Mean per traced step blocked in ``*.readback``. Less
``decode_step_device_ms`` + ``prefill_step_device_ms`` it is the
launch-to-result latency the host adds to the device's programs."""


def read(ctx):
    from benchmark import engine_phases
    return engine_phases.mean_ms(ctx, engine_phases.WAIT)
