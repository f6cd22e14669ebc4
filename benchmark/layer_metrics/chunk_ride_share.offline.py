"""Of the traced steps that dispatched a prefill chunk (a
``prefill.dispatch`` or a ``mixed.dispatch`` phase in the step's
``engine_step`` record), the share in percent whose chunk rode with the
decode batch in ONE program (``mixed.dispatch``): how often the second
read of the weights was not made. A program with no such phase reads
0; traced steps with no chunk at all give nothing to read."""


def read(ctx):
    from benchmark import engine_phases
    recs = engine_phases.traced_records(ctx)
    if recs is None:
        return None
    carried = [names for names in ({p[0] for p in r["phases"]} for r in recs)
               if names & {"prefill.dispatch", "mixed.dispatch"}]
    if not carried:
        return None
    return 100.0 * sum("mixed.dispatch" in n for n in carried) / len(carried)
