"""The sink-softmax, split-width expert cell cut to a size the CPU holds
in seconds (``tests/test_chip_compile.py`` calls it by name): the same
block in small — 5 layers ``full, sliding x 3, full`` (the first dense),
8 query heads over 2 KV heads on a full layer and 4 on a sliding one,
a key head of 24 lanes (the first ``int(24 x 0.334)`` = 8 rotated) beside
a value head of 16, a window of 16 positions with a sink a head, 4 of
16 experts held (the second quarter: ``expert_first`` 4) with the top 4
by sigmoid score and choice bias and no shared one — float32, 4 slots
of 96 positions, answers of 24 to 64 tokens so that every sequence runs
far past the window and its ring of 3 blocks turns over several times.

Steady under load by construction, as ``shrink_laguna.py``: float32
weights served from FLOAT32 pools, so the program and the plain
reference differ by the order of their sums alone whichever requests a
window happens to complete, and ``step_clock`` makes the window itself
a count of steps (``shrink_laguna.StepClock``) and no wall-clock span."""

from .shrink_evabyte import step_clock  # noqa: F401  (the caller's)

# initializer_range: at d=64 the published 0.02 leaves the blocks'
# outputs too small to rule the logits; 0.2 makes them rule
TINY = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            num_attention_heads=8, swa_num_attention_heads=8,
            num_key_value_heads=2, swa_num_key_value_heads=4,
            head_dim=24, swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
            n_routed_experts=4, router_experts=16, expert_first=4,
            num_experts_per_tok=4, num_hidden_layers=5,
            sliding_window=16, sliding_window_size=16,
            hybrid_layer_pattern=[0, 1, 1, 1, 0],
            moe_layer_freq=[0, 1, 1, 1, 1],
            vocab_size=96, max_position_embeddings=96,
            initializer_range=0.2)


def serve(cell: dict) -> None:
    cell["config"].update(TINY)
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
