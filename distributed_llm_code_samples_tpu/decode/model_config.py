"""An engine from a published-style ``config.json``.

ONE function builds the engine for ``train_ffns.py generate
--model_config FILE`` and for the benchmark's driver file
(``benchmark/configs/jamba_engine_driver.py``,
``glm_moe_engine_driver.py``, ``lfm2_moe_engine_driver.py``,
``laguna_engine_driver.py``, ``evabyte_engine_driver.py``,
``mimo_v2_flash_engine_driver.py``, ``qwen3_next_engine_driver.py``): the
published keys say what the model is
(``model_type`` picks the family's file under ``models/``, its
``spec_from_config`` reads the rest), the weights come from a seed or from the caller (a checkpoint restored into
the seeded tree, the benchmark's own arrays), and every engine tunable
keeps the program's default unless the caller's ``EngineConfig`` says
otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import (evabyte_lm, hybrid_lm, laguna_lm, lfm2_moe_lm,
                      mimo_v2_flash_lm, mla_moe_lm, qwen3_next_lm)
from .engine import DecodeEngine, EngineConfig

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

# model_type -> (the published keys as sizes, seeded weights of them)
FAMILIES = {
    "jamba": (hybrid_lm.spec_from_config, hybrid_lm.init_hybrid_lm),
    "glm4_moe_lite": (mla_moe_lm.spec_from_config,
                      mla_moe_lm.init_mla_moe_lm),
    "lfm2_moe": (lfm2_moe_lm.spec_from_config,
                 lfm2_moe_lm.init_lfm2_moe_lm),
    "laguna": (laguna_lm.spec_from_config, laguna_lm.init_laguna_lm),
    "evabyte": (evabyte_lm.spec_from_config, evabyte_lm.init_evabyte_lm),
    "mimo_v2_flash": (mimo_v2_flash_lm.spec_from_config,
                      mimo_v2_flash_lm.init_mimo_v2_flash_lm),
    "qwen3_next": (qwen3_next_lm.spec_from_config,
                   qwen3_next_lm.init_qwen3_next_lm),
}


def weights_dtype(config: dict):
    """The type the configuration states its weights in
    (``precision.weights`` as the benchmark's files write it, or the
    published ``torch_dtype``); float32 where it states none."""
    name = (config.get("precision", {}).get("weights")
            or config.get("torch_dtype") or "float32")
    if name not in DTYPES:
        raise ValueError(f"weights type {name!r} not in {sorted(DTYPES)}")
    return DTYPES[name]


def params_from_config(config: dict, seed: int = 0):
    """Seeded weights of the configured model, in its stated type."""
    family = FAMILIES.get(config.get("model_type"))
    if family is None:
        raise ValueError(f"model_type {config.get('model_type')!r}: "
                         f"served are {sorted(FAMILIES)}")
    spec_from_config, init = family
    spec = spec_from_config(config)
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return init(
        key, spec, dtype=weights_dtype(config),
        scale=float(config.get("initializer_range",
                               config.get("init_std", 2e-2))))


def engine_from_config(config: dict, params=None, *, seed: int = 0,
                       engine_config: EngineConfig | None = None,
                       **engine_kw) -> DecodeEngine:
    """``DecodeEngine`` for the model ``config`` describes, over
    ``params`` (default: seeded from ``seed``)."""
    if params is None:
        params = params_from_config(config, seed)
    return DecodeEngine(params, int(config["num_attention_heads"]),
                        engine_config, **engine_kw)
