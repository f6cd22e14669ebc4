"""Plain reference for the paper's FFN stack (``train_ffns.py``).

A stack of bias-free ``x -> relu(x @ w1.T) @ w2.T`` blocks (no
residual, no norm: the reference repository's model), trained by SGD
on a mocked upstream gradient: a step draws ``x`` and ``dloss/dy`` from
its integer seed and moves each weight by ``-lr * dW`` where ``dW`` is
the vector-Jacobian product of the stack with ``dloss/dy`` (sums over
tokens; there is no scalar loss). float32, ``highest`` precision,
``jax.vjp`` of the plain forward; nothing imported from the program.

``batch(seed, ...)`` restates the program's data contract
(``data/__init__.py::batch_from_seed``): the data IS the seed, so the
reference must draw the same rows the trainer draws inside its step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
DLOSS_DY_COEF = 0.1


def batch(seed, tokens: int, d: int):
    key = jax.random.fold_in(jax.random.PRNGKey(0), jnp.asarray(seed))
    kx, kd = jax.random.split(key)
    return (jax.random.normal(kx, (tokens, d)),
            DLOSS_DY_COEF * jax.random.normal(kd, (tokens, d)))


def forward(w: dict, x):
    for l in range(w["w1"].shape[0]):
        h = jnp.maximum(jnp.matmul(x, w["w1"][l].T, precision=HI), 0)
        x = jnp.matmul(h, w["w2"][l].T, precision=HI)
    return x


def _shape(config: dict) -> dict:
    return {"tokens": config["batch_size"] * config["seq_len"],
            "d": config["model_size"]}


@partial(jax.jit, static_argnames=("tokens", "d"))
def _grads(w: dict, seed, *, tokens: int, d: int):
    x, dy = batch(seed, tokens, d)
    _, vjp = jax.vjp(lambda p: forward(p, x), w)
    return vjp(dy)[0]


def grads(w: dict, seed, config: dict) -> dict:
    """The step's weight gradients under ``w``'s names, by ``jax.vjp``
    of the plain forward at float32 ``highest``."""
    return _grads(w, seed, **_shape(config))


def _low(a, mode: str):
    """An operand as a lower-precision matrix unit would take it:
    ``bf16`` rounds it to bfloat16, ``int8`` to the 255 levels of a
    symmetric per-row int8 scale."""
    if mode == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


def grads_low(w: dict, seed, config: dict, mode: str) -> dict:
    """The same gradients with every matrix product, forward and
    backward, taking its two operands in a lower precision (float32
    accumulation): the control that ``correct`` has to refuse. Written
    out by hand, since rounding has no derivative."""
    return _grads_low(w, seed, mode=mode, **_shape(config))


@partial(jax.jit, static_argnames=("tokens", "d", "mode"))
def _grads_low(w: dict, seed, *, tokens: int, d: int, mode: str):
    x, dy = batch(seed, tokens, d)
    mm = lambda a, b: jnp.matmul(_low(a, mode), _low(b, mode),  # noqa: E731
                                 precision=HI)
    layers = w["w1"].shape[0]
    xs, pres = [], []
    for l in range(layers):
        xs.append(x)
        pre = mm(x, w["w1"][l].T)
        pres.append(pre)
        x = mm(jnp.maximum(pre, 0), w["w2"][l].T)
    g1, g2 = [None] * layers, [None] * layers
    for l in reversed(range(layers)):
        h = jnp.maximum(pres[l], 0)
        g2[l] = mm(dy.T, h)
        dpre = mm(dy, w["w2"][l]) * (pres[l] > 0)
        g1[l] = mm(dpre.T, xs[l])
        dy = mm(dpre, w["w1"][l])
    return {"w1": jnp.stack(g1), "w2": jnp.stack(g2)}


def update(w: dict, g: dict, lr: float) -> dict:
    """The optimizer, restated: plain SGD."""
    return {k: w[k] - lr * g[k] for k in w}
