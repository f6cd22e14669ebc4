"""The benchmark's yardsticks, held to what they measure in the run that
gates every PR.

``benchmark/tests/`` is the benchmark's own suite and the driver's test
command collects ``tests/`` only. This file brings in, as they stand and
under their file's name, the tests there that run no cell: the trace
reducer on its recorded fixtures (``test_xplane``), the join of programs
to launches by ordinal and its late-read form (``test_dispatch_join_late``,
``test_recorded_windows``), the readers that book a program's ops
(``test_expert_trace_by_ordinal``, ``test_chunk_trace``,
``test_sink_window_trace``, ``test_delta_trace``), the shares read
from step records (``test_chunk_ride_share``, ``test_late_read_share``),
the trainer's FLOP count against the compiled step
(``test_trainer_flops``), and the refusal to measure off a TPU
(``test_rehearsal::test_refuses_to_run_off_a_tpu``). Every parametrised
case stays a case of its own; fixtures are found where they lie, beside
those files.

What it buys: a change to the package that makes a yardstick's count
stale (a product the trainer's step no longer holds, a program the join
can no longer find) fails here with the count's name, before any chip
time is spent, so the ``benchmark`` issue that corrects the yardstick is
written first and a sound change is not refused for a reading the chip
cannot give.

NOT here: the rehearsals (the rest of ``test_rehearsal``,
``test_window_trace``, ``test_engine_phases``). They run whole cells on
the CPU for 10-24 s a case and share ``.bench_trace`` between processes,
so they are not yet steady beside other workers; a ``benchmark`` issue
that makes them so can add their files to ``FILES``.
"""

import importlib

# file of benchmark/tests -> the tests taken from it (() for all of them)
FILES = {"test_xplane": (), "test_dispatch_join_late": (),
         "test_trainer_flops": (), "test_expert_trace_by_ordinal": (),
         "test_chunk_ride_share": (), "test_late_read_share": (),
         "test_chunk_trace": (), "test_recorded_windows": (),
         "test_sink_window_trace": (), "test_delta_trace": (),
         "test_rehearsal": ("test_refuses_to_run_off_a_tpu",)}


def _bring_in(file: str, only: tuple) -> None:
    """Put ``benchmark/tests/<file>.py``'s tests into this module as
    ``<file>__<name>``, with the fixtures they ask for; ``only`` names
    the tests taken from a file that also holds rehearsals (whose
    fixtures stay behind)."""
    module = importlib.import_module("benchmark.tests." + file)
    for name, obj in vars(module).items():
        if name.startswith("test_") and callable(obj):
            if not only or name in only:
                globals()[f"{file}__{name[len('test_'):]}"] = obj
        elif not only and type(obj).__module__ == "_pytest.fixtures":
            globals()[name] = obj


for _file, _only in FILES.items():
    _bring_in(_file, _only)
