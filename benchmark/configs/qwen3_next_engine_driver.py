"""How a serving cell reaches the gated delta-rule and gated
full-attention, fine-grained sparse-expert LM (``model_type:
qwen3_next``): the engine that ``train_ffns.py generate --model_config
<config.json>`` builds, through the same library function
(``decode/model_config.py::engine_from_config``), on one chip. Only the
model and its capacity are set; every tunable keeps the program's
default.

The weights are the program's own seeded arrays in the type the
configuration serves them in (``models/qwen3_next_lm.py::
init_qwen3_next_lm``: the configuration's ``assumed`` says what it
draws), handed to the engine and to the plain reference alike as named
leaves, every matrix ``[out, in]``, stacked over the layers of their
kind. The held experts and the held slice of the vocabulary are the
configuration's (``num_experts``, ``vocab_size``): the reference is
given the same.
"""

from __future__ import annotations

STACKS = ("delta", "full", "experts", "shared")
TOP = ("wte", "w_head", "norm_in", "norm_ff", "g_f", "w_gate", "g_q", "g_k",
       "w_sg")


def make_weights(config: dict, seed: int) -> dict:
    from distributed_llm_code_samples_tpu.decode.model_config import (
        params_from_config)
    p = params_from_config(config, seed)
    w = {k: getattr(p, k) for k in TOP}
    for stack in STACKS:
        for k, x in getattr(p, stack)._asdict().items():
            if x is not None:           # the router has no choice bias
                w[f"{stack}.{k}"] = x
    return w


def _params(config: dict, w: dict):
    from distributed_llm_code_samples_tpu.models import qwen3_next_lm as m
    from distributed_llm_code_samples_tpu.models.face import (AttnStack,
                                                               MLPStack)
    spec = m.spec_from_config(config)
    kinds = {"delta": m.DeltaStack, "full": AttnStack,
             "experts": m.ExpertStack, "shared": MLPStack}
    stacks = {s: kinds[s](**{f: w.get(f"{s}.{f}") for f in kinds[s]._fields})
              for s in STACKS}
    return m.Qwen3NextLMParams(
        **{k: w[k] for k in TOP}, **stacks, kinds=spec.kinds,
        head_dim=spec.head_dim, rotary=spec.rotary,
        key_heads=spec.key_heads, key_dim=spec.key_dim, top_k=spec.top_k,
        eps=spec.eps, max_seq_len=spec.max_seq_len,
        expert_first=spec.expert_first)


def engine_config(config: dict):
    from distributed_llm_code_samples_tpu.decode.engine import EngineConfig
    serving = config["serving"]
    block = EngineConfig().block_size
    per_seq = -(-serving["max_positions"] // block)
    return EngineConfig(n_blocks=1 + serving["max_slots"] * per_seq,
                        max_slots=serving["max_slots"],
                        max_blocks_per_seq=per_seq,
                        kv_dtype=serving["kv_dtype"])


def build_engine(config: dict, w: dict, metrics=None):
    from distributed_llm_code_samples_tpu.decode.model_config import (
        engine_from_config)
    return engine_from_config(config, _params(config, w),
                              engine_config=engine_config(config),
                              metrics=metrics)


def decode_weight_bytes(w: dict) -> int:
    """Bytes of weights one decode dispatch has to read if it touches
    every held expert, from the arrays' own dtypes: every leaf once but
    the embedding, of which a row a token is read (the head is a matrix
    of its own)."""
    return int(sum(x.size * x.dtype.itemsize for k, x in w.items()
                   if k != "wte"))
