"""How a training cell reaches the paper's FFN stack on one device
(``train_ffns.py -m 1``): the strategy function the CLI dispatches to
(``parallel.STRATEGIES[1]``, ``train_single``) with the keyword
arguments ``cli.main`` would hand it at the program's defaults.
``cli.main`` itself cannot be timed from outside (one call initialises,
compiles, trains and prints), so these dozen lines restate how it calls
the strategy; PERF.md lists the library entry the CLI lacks.

A configuration names this file under ``driver``; ``benchmark/train.py``
knows no model, method or leaf by name. A trainer for another method or
model arrives as another file like this one, with its reference.
"""

from __future__ import annotations

from benchmark import flops, weights


def make_weights(config: dict, seed: int) -> dict:
    """The initial parameters as named leaves, made on the device from
    the seed in one jitted call. The plain reference starts from the
    same call."""
    return weights.ffn_weights(
        weights.key_of(seed), d=config["model_size"], layers=config["layers"],
        ffn=config.get("ffn_size", 4 * config["model_size"]))


def build(config: dict, w: dict) -> dict:
    """The job as ``cli.main`` would build it from ``config["argv"]``:

    - ``step(state, seeds) -> state``: one call of the program's trainer
      over the integer step seeds (the data IS the seed);
    - ``state``: the initial state, holding the arrays of ``w``;
    - ``leaves(state)``: the state's parameters under ``w``'s names;
    - ``grad_of_step(before, after)``: one leaf's first gradient as the
      optimizer got it, from the leaf before and after one step (SGD);
    - ``lr``, ``tokens_per_step``, ``name``."""
    from distributed_llm_code_samples_tpu import LR, cli
    from distributed_llm_code_samples_tpu.models.ffn_stack import (
        FFNStackParams)
    from distributed_llm_code_samples_tpu.parallel import STRATEGIES
    args = cli.build_parser().parse_args(config["argv"])
    lr = LR if args.lr is None else args.lr
    name, fn = STRATEGIES[args.method]
    tokens = args.batch_size * args.seq_len

    def step(state, seeds):
        return fn(state, seeds, tokens, args.model_size, lr=lr,
                  unroll=not args.scan)

    return {"name": name, "step": step,
            "state": FFNStackParams(w["w1"], w["w2"]),
            "leaves": lambda state: {"w1": state.w1, "w2": state.w2},
            "grad_of_step": lambda before, after: (before - after) / lr,
            "lr": lr, "tokens_per_step": tokens}


def flops_per_token(config: dict) -> float:
    """Model FLOPs one trained token needs (``benchmark/flops.py``)."""
    return flops.ffn_train_flops_per_token(config)
