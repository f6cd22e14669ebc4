"""How a serving cell reaches the chunk-summarised (EVA) attention byte
LM (``model_type: evabyte``): the engine that ``train_ffns.py generate
--model_config <config.json>`` builds, through the same library function
(``decode/model_config.py::engine_from_config``), on one chip. Only the
model and its capacity are set; every tunable keeps the program's
default.

The weights are the program's own seeded arrays in the type the
configuration serves them in (``models/evabyte_lm.py::init_evabyte_lm``:
the configuration's ``assumed.weights`` says what it draws), handed to
the engine and to the plain reference alike as named leaves, every
matrix ``[out, in]``, stacked over the layers.

A sequence's capacity is counted in POSITIONS (``serving.max_positions``)
and held in two stores: a ring of the current window's blocks a slot,
and one ROW of the full kind's pool for every chunk of ``chunk_size``
positions, so a slot's table of that pool is ``ceil(ceil(positions /
chunk) / block)`` blocks.
"""

from __future__ import annotations

STACKS = ("attn", "mlp")
TOP = ("wte", "w_head", "norm_in", "norm_ff", "g_f", "phi", "mu")


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
    from distributed_llm_code_samples_tpu.models import evabyte_lm as m
    from distributed_llm_code_samples_tpu.models.face import (AttnStack,
                                                               MLPStack)
    spec = m.spec_from_config(config)
    kinds = {"attn": AttnStack, "mlp": MLPStack}
    stacks = {s: kinds[s](**{k.split(".", 1)[1]: x for k, x in w.items()
                             if k.startswith(s + ".")}) for s in STACKS}
    return m.EvaByteLMParams(
        **{k: w[k] for k in TOP}, **stacks, head_dim=spec.head_dim,
        window=spec.window, chunk=spec.chunk, theta=spec.theta, eps=spec.eps,
        unit_offset=spec.unit_offset, max_seq_len=spec.max_seq_len)


def engine_config(config: dict):
    from distributed_llm_code_samples_tpu.decode.engine import EngineConfig
    serving = config["serving"]
    block = EngineConfig().block_size
    rows = -(-serving["max_positions"] // int(config["chunk_size"]))
    per_seq = -(-rows // block)
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


def ring_blocks(config: dict) -> int:
    """Entries of a slot's ring as ``build_engine`` sizes it: the
    window's blocks, the one being written, one of slack."""
    from distributed_llm_code_samples_tpu.decode.programs import (
        window_entries)
    return window_entries(engine_config(config), int(config["window_size"]),
                          True)


def decode_weight_bytes(w: dict) -> int:
    """Bytes of weights one decode dispatch has to read, from the
    arrays' own dtypes: every leaf once but the embedding, of which a
    row a token is read, and of the head the FIRST prediction head's
    rows only (the seven further heads are computed by no step
    program)."""
    rows = w["wte"].shape[0]
    total = sum(x.size * x.dtype.itemsize for k, x in w.items()
                if k not in ("wte", "w_head"))
    head = w["w_head"]
    return int(total + rows * head.shape[1] * head.dtype.itemsize)
