"""The gated-convolution, sparse-expert cell cut to a size the CPU holds
in seconds (``shrink.py`` knows the GPT-2 and FFN cells only, and is not
edited): the same block in small — 6 layers ``conv, full_attention,
conv, conv, full_attention, conv`` (the first dense), 4 query heads over
2 KV heads of 16 lanes, 16 experts of 48 with the top 4 — float32, 4
slots of 64 positions.

Steady under load by construction: the toy serves float32 weights from
a FLOAT32 pool, so the program and the plain reference differ by the
order of their sums alone (1e-5 on logits) whichever requests the
rehearsal's wall-clock window happens to complete. (The latent cell's
rehearsal keeps a bfloat16 pool; at toy width one expert choice flipped
by a bfloat16 row reads 0.09-0.8 against its limit of 0.05, and which
requests are compared follows the machine's speed: PERF.md section 7.)"""

# initializer_range: at d=64 the published 0.02 leaves the blocks'
# outputs too small to rule the logits; 0.2 makes them rule, as they do
# at d=2048
TINY = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2, num_experts=16,
            num_hidden_layers=6,
            layer_types=["conv", "full_attention", "conv", "conv",
                         "full_attention", "conv"],
            vocab_size=96, max_position_embeddings=64,
            initializer_range=0.2)


def serve(cell: dict) -> None:
    cell["config"].update(TINY)
    cell["config"]["precision"]["weights"] = "float32"
    cell["config"]["serving"].update(max_slots=4, max_positions=64,
                                     kv_dtype="f32")
    work = cell["work"]
    work["traffic"].update(
        prompt_len={"dist": "zipf", "alpha": 1.3, "lo": 4, "hi": 24},
        max_new={"dist": "uniform", "lo": 4, "hi": 16},
        max_total=64, block=16)
    work["traffic"]["arrival"]["n"] = 4000
    work["preroll"]["completed"] = 4
    work["trace"].update(after_s=0.2, for_s=0.4)
    # float32 end to end: 0.0 and 0.0 were read on 24 of 24 seeds (0-23,
    # six processes at once on eight cores): every served token was the
    # reference's first; a near-tie would read ~1e-5
    work["correct"].update(sample=3, pad_to=16, max_logit_gap=2e-3,
                           mean_logit_gap=2e-4)
