"""Of the step programs the traced steps launched, the share in percent
whose result was read in a LATER step than the one that launched it:
how often the device had its next program queued before the host
waited for the last one (``decode/engine.py::_launch`` /
``_collect``). The ``engine_step`` record says which launches a step
read (``readbacks``: ordinals among the engine's launches) and how
many the engine had made by the step's end (``launches``), so a step's
own launches are the last ``len(dispatches)`` ordinals below that; one
that is not among the step's ``readbacks`` was read later (every launch
is read once, and never before it is made). A program whose records
hold no ``readbacks`` reads every result in the step that launched it
(``runtime/tracing.py``'s contract before telemetry v20): 0. Traced
steps that launched nothing give nothing to read."""


def read(ctx):
    from benchmark import engine_phases
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    launched = late = 0
    for rec in recs:
        n = sum(p[0].endswith(".dispatch") for p in rec["phases"])
        launched += n
        if "readbacks" in rec:
            first = rec["launches"] - n
            late += n - sum(first <= o for o in rec["readbacks"])
    if not launched:
        return None
    return 100.0 * late / launched
