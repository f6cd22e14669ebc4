"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the system under test
and the plain reference are handed the same arrays, so the reference
takes nothing the program made. ``scale * normal`` with LayerNorm gains
at 1 — the program's own init family (``models/lm.py::init_lm``), at
the precision the configuration serves or trains in.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SCALE = 2e-2


def key_of(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


@partial(jax.jit, static_argnames=("vocab", "d", "layers", "inner",
                                   "positions", "dtype", "scale"))
def lm_weights(key, *, vocab: int, d: int, layers: int, inner: int,
               positions: int, dtype=jnp.float32,
               scale: float = SCALE) -> dict:
    """GPT-2-shaped, bias-free: every matrix ``[out, in]``, stacked
    over layers. At the published widths and ``scale`` 0.02 every FFN
    sublayer adds about 0.7 of standard deviation to a residual stream
    that starts at 0.03, so the final logits are the network's work and
    not the tied embedding's self-product (which rules a toy width:
    the tests raise ``scale`` there)."""
    ks = jax.random.split(key, 8)

    def w(k, *shape):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    ones = jnp.ones((layers, d), dtype)
    return {"wte": w(ks[0], vocab, d), "wpe": w(ks[1], positions, d),
            "ln1": ones, "wq": w(ks[2], layers, d, d),
            "wk": w(ks[3], layers, d, d), "wv": w(ks[4], layers, d, d),
            "wo": w(ks[5], layers, d, d), "ln2": ones,
            "w1": w(ks[6], layers, inner, d), "w2": w(ks[7], layers, d, inner),
            "ln_f": jnp.ones((d,), dtype)}


@partial(jax.jit, static_argnames=("d", "layers", "ffn", "dtype"))
def ffn_weights(key, *, d: int, layers: int, ffn: int,
                dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w1": (SCALE * jax.random.normal(k1, (layers, ffn, d),
                                             jnp.float32)).astype(dtype),
            "w2": (SCALE * jax.random.normal(k2, (layers, d, ffn),
                                             jnp.float32)).astype(dtype)}
