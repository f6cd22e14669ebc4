"""Every cell cut to a size the CPU holds in seconds. The sizes change
here, in the tests, and nowhere else: the harness has no option for it."""

# init_scale: at d=32 the published 0.02 leaves the logits ruled by the
# tied embedding's self-product (every greedy pick repeats its input);
# 0.35 makes the blocks' outputs rule, as they do at d=1280
TINY_LM = dict(n_embd=32, n_head=4, n_layer=2, vocab_size=64,
               n_positions=64, n_ctx=64, n_inner=None, init_scale=0.35)


def serve(cell: dict) -> None:
    cell["config"].update(TINY_LM)
    cell["config"]["serving"].update(max_slots=4, max_positions=64)
    work = cell["work"]
    work["traffic"].update(
        prompt_len={"dist": "zipf", "alpha": 1.3, "lo": 4, "hi": 24},
        max_new={"dist": "uniform", "lo": 4, "hi": 16},
        max_total=64, block=16)
    arrival = work["traffic"]["arrival"]
    if arrival["kind"] == "backlog":
        arrival["n"] = 4000
        work["preroll"]["completed"] = 4
    else:
        arrival["rate"] = 20.0
        work["preroll"]["seconds"] = 0.5
        work["drain_s"] = 5.0
    work["trace"].update(after_s=0.2, for_s=0.4)
    # float32 on the CPU but a bfloat16 KV pool: 0.004 and 0.0001 were read
    work["correct"].update(sample=3, pad_to=16, max_logit_gap=0.05,
                           mean_logit_gap=2e-3)


def serve_open(cell: dict) -> None:
    """The offline cell turned into an open Poisson loop: no cell of
    ``BENCHMARK.json`` is one yet (PERF.md, Open questions), the kind's
    control flow is rehearsed all the same."""
    work = cell["work"]
    work["kind"] = "serve-open"
    work["traffic"]["arrival"] = {"kind": "poisson", "rate": 20.0}
    work["preroll"] = {"seconds": 0.5}
    work["drain_s"] = 5.0
    serve(cell)


def train_ffn(cell: dict) -> None:
    cell["config"].update(
        model_size=32, ffn_size=128, layers=2, seq_len=8, batch_size=4,
        # --lr 0.1: at the default 1e-5 a toy gradient's update drowns in
        # float32's rounding of the weights it is recovered from
        argv=["-m", "1", "-d", "32", "-l", "2", "-n", "8", "-bs", "4",
              "--lr", "0.1"])
    cell["work"]["trace"].update(after_s=0.2, for_s=0.3)
    # float32 on the CPU: 3e-5, 1e-6 and 0 were read
    cell["work"]["correct"].update(
        grad_rel_diff={"w1": {"limit": 1e-3},
                       "w2": {"limit": 1e-3, "layer": -1}},
        grad_norm_gap=1e-4,
        param_change_gap=1e-4)


def for_cell(name: str):
    return train_ffn if name.startswith("ffn") else serve
