"""The chunk-summarised attention cell cut to a size the CPU holds in
seconds (``shrink.py`` knows the GPT-2 and FFN cells only, and is not
edited): the same block in small — 2 layers, 4 heads of 16 lanes, a
window of 64 positions in chunks of 16, all 320 byte ids and all 8
prediction heads — float32, 4 slots of 256 positions, answers of 100 to
200 bytes, so that every sequence crosses one to three window
boundaries, its ring of 6 blocks turns over and its later rows join up
to 12 summaries to their window.

Steady under load by construction, as ``shrink_laguna.py``: float32
weights served from FLOAT32 pools, so the program and the plain
reference differ by the order of their sums alone whichever requests a
window happens to complete, and ``step_clock`` makes the window itself a
count of steps (``shrink_laguna.StepClock``) and no wall-clock span."""

from .shrink_laguna import StepClock

# init_std: at d=64 the published 0.01275 leaves the blocks' outputs too
# small to rule the logits; 0.3 makes them rule
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=4, num_hidden_layers=2, window_size=64,
            max_position_embeddings=256, init_std=0.3)


def serve(cell: dict) -> None:
    cell["config"].update(TINY)
    cell["config"]["precision"]["weights"] = "float32"
    cell["config"]["serving"].update(max_slots=4, max_positions=256,
                                     kv_dtype="f32")
    work = cell["work"]
    work["traffic"].update(
        prompt_len={"dist": "zipf", "alpha": 1.3, "lo": 4, "hi": 40},
        max_new={"dist": "uniform", "lo": 100, "hi": 200},
        max_total=256, block=16)
    work["traffic"]["arrival"]["n"] = 4000
    work["preroll"]["completed"] = 2
    work["trace"].update(after_s=0.2, for_s=0.8)
    # float32 end to end: every served byte the reference's first on
    # the seeds read (a near-tie would read ~1e-5)
    work["correct"].update(sample=3, pad_to=16, max_logit_gap=2e-3,
                           mean_logit_gap=2e-4)


def step_clock(monkeypatch, tick: float = 0.01) -> StepClock:
    """``benchmark/serve.py`` and ``benchmark/harness.py`` on a clock
    that moves ``tick`` seconds an engine step: a window of ``s``
    seconds is ``s / tick`` steps on any machine."""
    from benchmark import harness, serve as serving
    clock = StepClock(tick)
    monkeypatch.setattr(serving, "time", clock)
    monkeypatch.setattr(harness, "time", clock)
    real_driver = harness.driver_module

    def watched(config):
        sut = real_driver(config)
        build = sut.build_engine
        sut.build_engine = lambda *a, **k: clock.watch(build(*a, **k))
        return sut

    monkeypatch.setattr(harness, "driver_module", watched)
    return clock
