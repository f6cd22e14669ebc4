"""A second trainer for the tests, nothing like the FFN stack: a linear
least-squares model with a bias and a scalar loss. Both of the files a
training configuration names in one module: the ``driver`` half (a
jitted ``jax.grad`` SGD step that returns ``(state, loss)``) and the
``reference`` half (the same mathematics written out by hand). It shows
that ``benchmark/train.py`` takes another model, other leaves and a
loss as files, with no edit."""

import jax
import jax.numpy as jnp

LR = 0.05


def _data(seed, config):
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(3),
                                                 jnp.asarray(seed)))
    n, d_in, d_out = config["rows"], config["d_in"], config["d_out"]
    return (jax.random.normal(k1, (n, d_in)),
            jax.random.normal(k2, (n, d_out)))


# -- the driver half -----------------------------------------------------

def make_weights(config, seed):
    k = jax.random.PRNGKey(int(seed) % (2**31))
    return {"kernel": 0.1 * jax.random.normal(
                k, (config["d_in"], config["d_out"])),
            "bias": jnp.zeros((config["d_out"],))}


def build(config, w):
    def loss_fn(p, seed):
        x, t = _data(seed, config)
        return jnp.mean(jnp.square(x @ p["kernel"] + p["bias"] - t))

    @jax.jit
    def step(state, seeds):
        loss = 0.0
        for s in seeds:
            loss, g = jax.value_and_grad(loss_fn)(state, s)
            state = {k: state[k] - LR * g[k] for k in state}
        return state, loss

    return {"name": "toy_sgd", "step": step, "state": dict(w),
            "leaves": lambda state: state, "returns_loss": True,
            "grad_of_step": lambda before, after: (before - after) / LR,
            "lr": LR, "tokens_per_step": config["rows"]}


def flops_per_token(config):
    return 6.0 * config["d_in"] * config["d_out"]


# -- the reference half --------------------------------------------------

def loss(w, seed, config):
    x, t = _data(seed, config)
    return jnp.mean(jnp.square(x @ w["kernel"] + w["bias"] - t))


def grads(w, seed, config):
    x, t = _data(seed, config)
    r = 2.0 * (x @ w["kernel"] + w["bias"] - t) / t.size
    return {"kernel": x.T @ r, "bias": r.sum(0)}


def update(w, g, lr):
    return {k: w[k] - lr * g[k] for k in w}
