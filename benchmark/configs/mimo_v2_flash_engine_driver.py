"""How a serving cell reaches the window-and-full-attention,
sink-softmax, sparse-expert LM whose K and V rows differ in width
(``model_type: mimo_v2_flash``): the engine that ``train_ffns.py
generate --model_config <config.json>`` builds, through the same library
function (``decode/model_config.py::engine_from_config``), on one chip.
Only the model and its capacity are set; every tunable keeps the
program's default.

The weights are the program's own seeded arrays in the type the
configuration serves them in (``models/mimo_v2_flash_lm.py::
init_mimo_v2_flash_lm``: the configuration's ``assumed`` says what it
draws), handed to the engine and to the plain reference alike as named
leaves, every matrix ``[out, in]``, stacked over the layers of their
kind. The held experts and the held slice of the vocabulary are the
configuration's (``n_routed_experts``, ``vocab_size``): the reference is
given the same.
"""

from __future__ import annotations

STACKS = ("full", "window", "dense", "experts")
TOP = ("wte", "w_head", "norm_in", "norm_ff", "g_f", "sinks")


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
    from distributed_llm_code_samples_tpu.models import mimo_v2_flash_lm as m
    from distributed_llm_code_samples_tpu.models.face import (AttnStack,
                                                               MLPStack)
    spec = m.spec_from_config(config)
    kinds = {"full": AttnStack, "window": AttnStack, "dense": MLPStack,
             "experts": m.ExpertStack}
    stacks = {s: kinds[s](**{k.split(".", 1)[1]: x for k, x in w.items()
                             if k.startswith(s + ".")}) for s in STACKS}
    return m.MimoV2FlashLMParams(
        **{k: w[k] for k in TOP}, **stacks, kinds=spec.kinds,
        dense_layers=spec.dense_layers, head_dim=spec.head_dim,
        v_head_dim=spec.v_head_dim, sliding_window=spec.sliding_window,
        rot_full=spec.rot_full, rot_window=spec.rot_window,
        value_scale=spec.value_scale, top_k=spec.top_k,
        routed_scale=spec.routed_scale, eps=spec.eps,
        max_seq_len=spec.max_seq_len, expert_first=spec.expert_first)


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
