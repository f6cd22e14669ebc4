"""Model FLOP/s utilisation: the FLOPs the forward and backward passes
need per token (``benchmark/flops.py``; recomputation not counted)
times the window's tokens per second, over chips times the chip's
published bf16 peak (``benchmark/peaks.json``)."""


def read(ctx):
    from benchmark import flops
    v = ctx["values"]
    if not v.get("train_tokens_per_s"):
        return None
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return (100.0 * v["model_flops_per_token"] * v["train_tokens_per_s"]
            / (v["chips"] * peak))
