"""Mean per traced step of the ``host``-class phases of
``engine.step()`` (expire, admit, the CoW barrier, marshal, the
bookkeeping after each dispatch, the digest): work that neither feeds
nor waits for the device."""


def read(ctx):
    from benchmark import engine_phases
    return engine_phases.mean_ms(ctx, engine_phases.HOST)
