"""The hybrid cell cut to a size the CPU holds in seconds (``shrink.py``
knows the GPT-2 and FFN cells only, and is not edited): the same layer
pattern in small — 6 layers with attention at ``i % 3 == 1``, 4 heads
over 1 KV head — float32, 4 slots of 64 positions."""

# initializer_range: at d=64 the published 0.02 leaves the logits ruled
# by the tied embedding's self-product; 0.2 makes the blocks' outputs
# rule, as they do at d=2560
TINY = dict(hidden_size=64, intermediate_size=128, mamba_d_state=4,
            mamba_dt_rank=8, num_hidden_layers=6, attn_layer_period=3,
            attn_layer_offset=1, num_attention_heads=4, vocab_size=96,
            max_position_embeddings=64, initializer_range=0.2)


def serve(cell: dict) -> None:
    cell["config"].update(TINY)
    cell["config"]["precision"]["weights"] = "float32"
    cell["config"]["serving"].update(max_slots=4, max_positions=64)
    work = cell["work"]
    work["traffic"].update(
        prompt_len={"dist": "zipf", "alpha": 1.3, "lo": 4, "hi": 24},
        max_new={"dist": "uniform", "lo": 4, "hi": 16},
        max_total=64, block=16)
    work["traffic"]["arrival"]["n"] = 4000
    work["preroll"]["completed"] = 4
    work["trace"].update(after_s=0.2, for_s=0.4)
    # float32 on the CPU but a bfloat16 KV pool under 2 of 6 layers
    work["correct"].update(sample=3, pad_to=16, max_logit_gap=0.05,
                           mean_logit_gap=2e-3)
