"""Mean per traced step of the ``launch``-class phases of
``engine.step()`` (``*.upload`` + ``*.dispatch``): operands to the
device and the jitted calls returning."""


def read(ctx):
    from benchmark import engine_phases
    return engine_phases.mean_ms(ctx, engine_phases.LAUNCH)
