"""How a serving cell reaches the gated short-convolution, grouped-query
attention, sparse-expert LM (``model_type: lfm2_moe``): the engine that
``train_ffns.py generate --model_config <config.json>`` builds, through
the same library function (``decode/model_config.py::
engine_from_config``), on one chip. Only the model and its capacity are
set; every tunable keeps the program's default.

The weights are the program's own seeded arrays in the type the
configuration serves them in (``models/lfm2_moe_lm.py::
init_lfm2_moe_lm``: the configuration's ``assumed.weights`` says what it
draws), handed to the engine and to the plain reference alike as named
leaves, every matrix ``[out, in]``, stacked over the layers of their
kind.
"""

from __future__ import annotations

STACKS = ("conv", "attn", "dense", "experts")
TOP = ("wte", "norm_in", "norm_ff", "g_f", "g_q", "g_k")


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
    from distributed_llm_code_samples_tpu.models import lfm2_moe_lm as m
    from distributed_llm_code_samples_tpu.models.face import (AttnStack,
                                                               MLPStack)
    spec = m.spec_from_config(config)
    kinds = {"conv": m.ConvStack, "attn": AttnStack, "dense": MLPStack,
             "experts": m.ExpertStack}
    stacks = {s: kinds[s](**{k.split(".", 1)[1]: x for k, x in w.items()
                             if k.startswith(s + ".")}) for s in STACKS}
    return m.Lfm2MoeLMParams(
        **{k: w[k] for k in TOP}, **stacks, kinds=spec.kinds,
        head_dim=spec.head_dim, top_k=spec.top_k,
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
    every expert, from the arrays' own dtypes: every leaf once (the
    embedding is the head)."""
    return int(sum(x.size * x.dtype.itemsize for x in w.values()))
