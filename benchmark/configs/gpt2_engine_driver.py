"""How a serving cell reaches the GPT-2-shaped LM: ``decode.DecodeEngine``
built as ``generate_cli`` builds it (``EngineConfig`` + the model) on one
chip. Only the model and its capacity are set; every tunable keeps the
program's default.

A configuration names this file under ``driver``; ``benchmark/serve.py``
drives whatever engine it returns through ``submit()`` / ``step()``. An
engine built another way (sharded over chips, another model family)
arrives as another file like this one.
"""

from __future__ import annotations

from benchmark import weights


def make_weights(config: dict, seed: int) -> dict:
    """The model's parameters as named leaves, made on the device from
    the seed in one jitted call, in the type they are served in. The
    plain reference is handed the same arrays."""
    import jax.numpy as jnp
    return weights.lm_weights(
        weights.key_of(seed), vocab=config["vocab_size"], d=config["n_embd"],
        layers=config["n_layer"],
        inner=config.get("n_inner") or 4 * config["n_embd"],
        positions=config["n_positions"],
        scale=config.get("init_scale", weights.SCALE),
        dtype={"float32": jnp.float32,
               "bfloat16": jnp.bfloat16}[config["precision"]["weights"]])


def build_engine(config: dict, w: dict, metrics=None):
    from distributed_llm_code_samples_tpu.decode.engine import (
        DecodeEngine, EngineConfig)
    from distributed_llm_code_samples_tpu.models.lm import LMParams
    from distributed_llm_code_samples_tpu.models.transformer import (
        TransformerParams)
    serving = config["serving"]
    block = EngineConfig().block_size
    per_seq = -(-serving["max_positions"] // block)
    cfg = EngineConfig(n_blocks=1 + serving["max_slots"] * per_seq,
                       max_slots=serving["max_slots"],
                       max_blocks_per_seq=per_seq,
                       kv_dtype=serving["kv_dtype"])
    params = LMParams(
        wte=w["wte"], wpe=w["wpe"], ln_f=w["ln_f"],
        blocks=TransformerParams(**{k: w[k] for k in (
            "ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w2")}))
    return DecodeEngine(params, config["n_head"], cfg, metrics=metrics)


def decode_weight_bytes(w: dict) -> int:
    """Bytes of weights one decode dispatch has to read, from the
    arrays' own dtypes: every leaf once but the position table (one row
    a sequence)."""
    return int(sum(x.size * x.dtype.itemsize
                   for k, x in w.items() if k != "wpe"))
