"""The decode program's share of its memory roofline: the bytes one
dispatch has to read (every weight once, the live keys and values once:
``benchmark/flops.py``, sizes from the arrays' own dtypes) over the
chip's published HBM bandwidth, against the device time one decode
dispatch took in the traced window. Decode at 32 slots is bound by
bytes, not by FLOPs (2 x 774M x 32 = 50 GFLOP against 3 GB)."""




def read(ctx):
    from benchmark import engine_trace, flops
    v = ctx["values"]
    ms = engine_trace.program_ms(ctx, "decode")
    if not ms:
        return None
    need = flops.lm_decode_step_bytes(v["weight_bytes"],
                                      v["kv_bytes_per_token"],
                                      v["traced_mean_live_tokens"])
    least_s = need / flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
