"""How a serving cell reaches the window-and-full-attention, gated-head,
sparse-expert LM (``model_type: laguna``): the engine that
``train_ffns.py generate --model_config <config.json>`` builds, through
the same library function (``decode/model_config.py::
engine_from_config``), on one chip. Only the model and its capacity are
set; every tunable keeps the program's default.

The weights are the program's own seeded arrays in the type the
configuration serves them in (``models/laguna_lm.py::init_laguna_lm``:
the configuration's ``assumed.weights`` says what it draws), handed to
the engine and to the plain reference alike as named leaves, every
matrix ``[out, in]``, stacked over the layers of their kind. The held
experts and the held slice of the vocabulary are the configuration's
(``num_experts``, ``vocab_size``): the reference is given the same.
"""

from __future__ import annotations

STACKS = ("full", "window", "dense", "shared", "experts")
TOP = ("wte", "w_head", "norm_in", "norm_ff", "g_f", "wg_full", "wg_window")


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
    from distributed_llm_code_samples_tpu.models import laguna_lm as m
    from distributed_llm_code_samples_tpu.models.face import (AttnStack,
                                                               MLPStack)
    spec = m.spec_from_config(config)
    kinds = {"full": AttnStack, "window": AttnStack, "dense": MLPStack,
             "shared": MLPStack, "experts": m.ExpertStack}
    stacks = {s: {k.split(".", 1)[1]: x for k, x in w.items()
                  if k.startswith(s + ".")} for s in STACKS}
    stacks["experts"].setdefault("bias", None)
    return m.LagunaLMParams(
        **{k: w[k] for k in TOP},
        **{s: kinds[s](**leaves) for s, leaves in stacks.items()},
        kinds=spec.kinds, dense_layers=spec.dense_layers,
        head_dim=spec.head_dim, sliding_window=spec.sliding_window,
        rot_full=spec.rot_full, rot_window=spec.rot_window,
        top_k=spec.top_k, routed_scale=spec.routed_scale, eps=spec.eps,
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


def window_pool_blocks(config: dict) -> int:
    """Usable blocks of the window layers' pool as ``build_engine``
    sizes it: a ring for every slot."""
    from distributed_llm_code_samples_tpu.decode.programs import (
        window_entries)
    cfg = engine_config(config)
    return cfg.max_slots * window_entries(cfg, int(config["sliding_window"]))


def decode_weight_bytes(w: dict) -> int:
    """Bytes of weights one decode dispatch has to read if it touches
    every held expert, from the arrays' own dtypes: every leaf once but
    the embedding, of which a row a token is read (the head is a matrix
    of its own)."""
    return int(sum(x.size * x.dtype.itemsize for k, x in w.items()
                   if k != "wte"))
