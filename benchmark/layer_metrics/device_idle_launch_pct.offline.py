"""Share of the traced window in which the device ran no op while the
program was in a ``launch``- or ``wait``-class phase (uploading
operands, the jitted call returning, blocked on the results): what
device-resident tables and fewer operands can remove."""


def read(ctx):
    from benchmark import engine_phases
    return engine_phases.idle_pct(
        ctx, (engine_phases.LAUNCH, engine_phases.WAIT))
