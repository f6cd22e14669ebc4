"""A hybrid model's decode program's share of its memory roofline: the
bytes one dispatch has to move (every weight once, the live keys and
values once, and each ready slot's recurrent state read once AND
written once) over the chip's published HBM bandwidth, against the
device time one decode dispatch took in the traced window. Weights and
KV from the arrays' own dtypes; the state's bytes from the program's
``engine_step`` records (``state_bytes`` is what a dispatch read).
``decode_step_roofline`` counts no state and stays with the models that
have none."""


def hybrid_decode_step_bytes(weight_bytes, kv_bytes_per_token,
                             live_tokens, state_read_bytes) -> float:
    return (float(weight_bytes) + float(kv_bytes_per_token) * live_tokens
            + 2.0 * float(state_read_bytes))


def read(ctx):
    from benchmark import engine_trace, flops, ssm_trace
    v = ctx["values"]
    state = ssm_trace.state_bytes(ctx)
    ms = engine_trace.program_ms(ctx, "decode")
    if state is None or not ms:
        return None
    need = hybrid_decode_step_bytes(v["weight_bytes"],
                                    v["kv_bytes_per_token"],
                                    v["traced_mean_live_tokens"], state)
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
