"""Share of the traced window in which the device ran no op while the
program was in a ``host``-class phase (gaps of the device's busy
intervals, each split over the phases that overlap it): what
scheduling the next step during the current one can remove."""


def read(ctx):
    from benchmark import engine_phases
    return engine_phases.idle_pct(ctx, (engine_phases.HOST,))
