"""The taken share of the window layers' pool: the blocks sequences
hold at a step's end (the program's ``window_blocks_live`` in its
``engine_step`` records, mean over the traced steps) over the pool's
usable blocks. ``kv_pool_util`` is of the full kind's pool."""


def read(ctx):
    from benchmark import window_trace
    return window_trace.pool_util_pct(ctx)
