"""The gated delta-rule, fine-grained expert cell cut to a size the CPU
holds in seconds (``tests/test_chip_compile.py`` calls it by name): the
same block in small — 4 layers ``delta x 3, full`` (one period), 2 key
and 4 value heads of 16 lanes in the delta layers (a state row of ``[16,
64]`` behind a convolution of 4 taps over 128 lanes), 4 query heads of
32 lanes over 2 KV heads in the full layer (the first 8 lanes rotated),
4 of 16 experts held (the second quarter: ``expert_first`` 4) with the
top 4 by softmax score beside a gated shared expert — float32, 4 slots
of 96 positions, answers of 24 to 64 tokens, so that every slot's state
row is reused several times without being cleared.

Steady under load by construction, as ``shrink_mimo.py``: float32
weights served from a FLOAT32 pool beside the float32 state, so the
program and the plain reference differ by the order of their sums alone
whichever requests a window happens to complete, and ``step_clock``
makes the window itself a count of steps and no wall-clock span."""

from .shrink_evabyte import step_clock  # noqa: F401  (the caller's)

# initializer_range: at d=64 the published 0.02 leaves the blocks'
# outputs too small to rule the logits; 0.2 makes them rule
TINY = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, num_experts=4, router_experts=16,
            expert_first=4, num_experts_per_tok=4, num_hidden_layers=4,
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
