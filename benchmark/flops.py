"""Operations and bytes an algorithm NEEDS, from its shapes.

The yardstick for every utilisation number the benchmark prints: model
FLOPs per trained token (recomputation never counts), and the bytes one
decode step has to read. Kept here, under the benchmark's own
directory, so no PR that claims a gain can change how its gain is
counted. ``peaks()`` is the one table of chip peaks, keyed by the exact
``device_kind``; an unknown kind is an error, never a default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"benchmark/peaks.json has no entry for device kind "
            f"{device_kind!r}: add its published peaks with their source")
    return table[device_kind]


def ffn_train_flops_per_token(model: dict) -> float:
    """Hand-VJP FFN stack, ``ffn = 4d``: forward 2 matmuls of 2*d*4d
    each, backward twice that (dx and dw per matmul). The program
    recomputes the pre-activation in its backward; that is not counted."""
    d, layers = model["model_size"], model["layers"]
    ffn = model.get("ffn_size", 4 * d)
    return 3.0 * layers * (2 * 2 * d * ffn)


def lm_decode_step_bytes(weight_bytes: int, kv_bytes_per_token: int,
                         live_tokens: float) -> float:
    """One decode dispatch must read every weight once (whatever the
    batch) and the keys and values of every live position once."""
    return float(weight_bytes) + float(kv_bytes_per_token) * live_tokens
