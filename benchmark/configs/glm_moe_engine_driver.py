"""How a serving cell reaches the latent-attention, sparse-expert LM
(``model_type: glm4_moe_lite``): the engine that ``train_ffns.py generate
--model_config <config.json>`` builds, through the same library function
(``decode/model_config.py::engine_from_config``), on one chip. Only the
model and its capacity are set; every tunable keeps the program's
default.

The weights are the program's own seeded arrays in the type the
configuration serves them in (``models/mla_moe_lm.py::init_mla_moe_lm``:
the configuration's ``assumed.weights`` says what it draws), handed to
the engine and to the plain reference alike as named leaves, every
matrix ``[out, in]``, stacked over the layers of their kind.
"""

from __future__ import annotations

STACKS = ("mla", "dense", "shared", "experts")
TOP = ("wte", "w_head", "norm_in", "norm_ff", "g_f")


def make_weights(config: dict, seed: int) -> dict:
    from distributed_llm_code_samples_tpu.decode.model_config import (
        params_from_config)
    p = params_from_config(config, seed)
    w = {k: getattr(p, k) for k in TOP}
    for stack in STACKS:
        for k, x in getattr(p, stack)._asdict().items():
            w[f"{stack}.{k}"] = x
    return w


def _params(config: dict, w: dict):
    from distributed_llm_code_samples_tpu.models import mla_moe_lm as m
    from distributed_llm_code_samples_tpu.models.face import MLPStack
    spec = m.spec_from_config(config)
    kinds = {"mla": m.MLAStack, "dense": MLPStack, "shared": MLPStack,
             "experts": m.ExpertStack}
    stacks = {s: kinds[s](**{k.split(".", 1)[1]: x for k, x in w.items()
                             if k.startswith(s + ".")}) for s in STACKS}
    return m.MlaMoeLMParams(
        **{k: w[k] for k in TOP}, **stacks, top_k=spec.top_k,
        routed_scale=spec.routed_scale, rope_theta=spec.rope_theta,
        eps=spec.eps, max_seq_len=spec.max_seq_len)


def build_engine(config: dict, w: dict, metrics=None):
    from distributed_llm_code_samples_tpu.decode.engine import EngineConfig
    from distributed_llm_code_samples_tpu.decode.model_config import (
        engine_from_config)
    serving = config["serving"]
    block = EngineConfig().block_size
    per_seq = -(-serving["max_positions"] // block)
    cfg = EngineConfig(n_blocks=1 + serving["max_slots"] * per_seq,
                       max_slots=serving["max_slots"],
                       max_blocks_per_seq=per_seq,
                       kv_dtype=serving["kv_dtype"])
    return engine_from_config(config, _params(config, w),
                              engine_config=cfg, metrics=metrics)


def decode_weight_bytes(w: dict) -> int:
    """Bytes of weights one decode dispatch has to read if it touches
    every expert, from the arrays' own dtypes: every leaf once but the
    embedding table, of which a dispatch reads one row a slot (the head
    is a leaf of its own)."""
    return int(sum(x.size * x.dtype.itemsize for k, x in w.items()
                   if k != "wte"))
