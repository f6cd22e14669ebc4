"""The window-and-full-attention expert cell cut to a size the CPU holds
in seconds (``shrink.py`` knows the GPT-2 and FFN cells only, and is not
edited): the same block in small — 4 layers ``full_attention,
sliding_attention x 3`` (the first dense), 4 and 6 gated query heads over
2 KV heads of 16 lanes, a window of 16 positions, 4 of 16 experts held
(the second quarter: ``expert_first`` 4) with the top 4 beside a shared
one — float32, 4 slots of 96 positions, answers of 24 to 64 tokens so
that every sequence runs far past the window and its ring of 4 blocks
turns over several times.

Steady under load by construction, as ``shrink_lfm2.py``: float32
weights served from a FLOAT32 pool, so the program and the plain
reference differ by the order of their sums alone whichever requests a
window happens to complete. ``StepClock`` makes the window itself a
count of steps and no wall-clock span."""

# initializer_range: at d=64 the published 0.02 leaves the blocks'
# outputs too small to rule the logits; 0.2 makes them rule
TINY = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            shared_expert_intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=4,
            router_experts=16, expert_first=4, num_experts_per_tok=4,
            num_hidden_layers=4, sliding_window=16,
            layer_types=["full_attention"] + ["sliding_attention"] * 3,
            mlp_layer_types=["dense"] + ["sparse"] * 3,
            num_attention_heads_per_layer=[4, 6, 6, 6],
            vocab_size=96, max_position_embeddings=96,
            initializer_range=0.2)


def serve(cell: dict) -> None:
    cell["config"].update(TINY)
    # YaRN's ramp over the toy's 8 rotated lanes: 32 original positions
    cell["config"]["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32)
    cell["config"]["precision"]["weights"] = "float32"
    cell["config"]["serving"].update(max_slots=4, max_positions=96,
                                     kv_dtype="f32")
    work = cell["work"]
    work["traffic"].update(
        prompt_len={"dist": "zipf", "alpha": 1.3, "lo": 4, "hi": 24},
        max_new={"dist": "uniform", "lo": 24, "hi": 64},
        max_total=96, block=16)
    work["traffic"]["arrival"]["n"] = 4000
    work["preroll"]["completed"] = 4
    work["trace"].update(after_s=0.2, for_s=0.4)
    # float32 end to end: every served token the reference's first on
    # the seeds read (a near-tie would read ~1e-5)
    work["correct"].update(sample=3, pad_to=16, max_logit_gap=2e-3,
                           mean_logit_gap=2e-4)


class StepClock:
    """A clock that moves by the engine's steps and by nothing else: a
    stand-in for the ``time`` module of ``benchmark/serve.py`` and
    ``benchmark/harness.py`` in a rehearsal. ``perf_counter`` reads
    ``tick`` seconds a step of ``engine`` (so a window of ``s`` seconds
    is ``s / tick`` steps on any machine, under any load); everything
    else is the real module's."""

    def __init__(self, tick: float = 0.01):
        import time
        self._time, self.tick, self.engine = time, tick, None

    def watch(self, engine):
        self.engine = engine
        return engine

    def perf_counter(self) -> float:
        return self.tick * (self.engine.steps if self.engine else 0)

    def __getattr__(self, name):
        return getattr(self._time, name)
