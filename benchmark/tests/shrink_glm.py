"""The latent-attention, sparse-expert cell cut to a size the CPU holds
in seconds (``shrink.py`` knows the GPT-2 and FFN cells only, and is not
edited): the same block in small — 4 layers (one dense), 4 heads over a
latent of 32 + 8 rotary lanes, 16 experts of 48 with the top 4 and one
shared — float32, 4 slots of 64 positions."""

# initializer_range: at d=64 the published 0.02 leaves the blocks'
# outputs too small to rule the logits; 0.2 makes them rule, as they do
# at d=2048
TINY = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=4,
            n_routed_experts=16, num_hidden_layers=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8,
            v_head_dim=16, vocab_size=96, max_position_embeddings=64,
            initializer_range=0.2)


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
    # float32 on the CPU but a bfloat16 latent pool under all 4 layers
    work["correct"].update(sample=3, pad_to=16, max_logit_gap=0.05,
                           mean_logit_gap=2e-3)
