"""The one traffic generator: a cell's traffic is a data file of
parameters, this module turns it and ``--seed`` into requests.

Every seed gets THE SAME multiset of sizes and arrival gaps, in another
order. Sizes are the stratified quantiles of the stated distribution
(``block`` of them), dealt block after block, each block a fresh seeded
permutation — so any window of a few blocks holds the same work, and
two seeds differ in order only. Arrival gaps are dealt the same way.
Token ids are drawn from the seed. jax-free (stdlib + numpy).

Traffic parameters (all under the workload file's ``traffic`` key):

- ``prompt_len`` / ``max_new``: ``{"dist": "fixed", "n": N}``,
  ``{"dist": "uniform", "lo": A, "hi": B}`` or
  ``{"dist": "zipf", "alpha": a, "lo": A, "hi": B}`` — ``lo - 1 +
  Zipf(a)`` clamped at ``hi`` (the grammar of the program's
  ``runtime/workload.py``, whose sampler this replaces).
- ``arrival``: ``{"kind": "backlog"}`` (all due at t=0),
  ``{"kind": "poisson", "rate": R}`` or ``{"kind": "bursty", "rate": R,
  "on_s": S, "off_s": S}`` (Poisson at R inside ON windows, silent OFF
  windows between).
- ``block``: sizes and gaps per permuted block (default 128).
- ``sessions``: ``{"count": K, "grow": G}`` — requests are dealt
  round-robin to K sessions, turn t of a session sends the first
  ``base + t*G`` tokens of that session's one token stream, so every
  turn regrows the previous turn's prompt as a shared prefix.
- ``max_total``: prompt + output never passes it (the longer of the
  pair is cut; with the stated distributions it never binds).
- ``order_seed``: where given, the ORDER of sizes and gaps is drawn from
  it and is the same in every run; ``--seed`` then draws the token ids
  (and the weights) only. For cells whose window holds so few requests
  that their order alone moves the result by a tenth (PERF.md, section
  4): the run becomes the replay of one fixed trace.
"""

from __future__ import annotations

import numpy as np


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (u = (i + 0.5) / n) of a length
    distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["n"]), np.int64)
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if hi < lo or lo < 1:
        raise ValueError(f"bad length range in {dist}")
    if kind == "uniform":
        return lo + np.minimum((u * (hi - lo + 1)).astype(np.int64), hi - lo)
    if kind == "zipf":
        alpha = float(dist["alpha"])
        if alpha <= 1.0:
            raise ValueError(f"zipf alpha must be > 1 in {dist}")
        k = np.arange(1, hi - lo + 1, dtype=np.float64)  # values below cap
        pmf = k ** -alpha
        # zeta(alpha): direct sum to 1e6 plus the integral tail
        big = np.arange(1, 1_000_001, dtype=np.float64)
        zeta = float(np.sum(big ** -alpha)) + 1e6 ** (1 - alpha) / (alpha - 1)
        cdf = np.cumsum(pmf) / zeta          # P(Z <= k), k < cap index
        idx = np.searchsorted(cdf, u, side="left")   # 0-based -> Z = idx+1
        return np.minimum(lo + idx, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def _gaps(arrival: dict, n: int) -> np.ndarray:
    """n stratified exponential gaps at the arrival's rate (seconds)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(arrival["rate"])


def generate(traffic: dict, n: int, seed: int, vocab: int) -> list[dict]:
    """``n`` requests: ``{"due_s", "prompt" (list of ids), "max_new"}``,
    ordered by due time."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x7EAF])
    order = (np.random.default_rng([int(traffic["order_seed"]), 0x0DE5])
             if "order_seed" in traffic else rng)
    block = int(traffic.get("block", 128))
    n_blocks = -(-n // block)
    plen_q = _quantiles(traffic["prompt_len"], block)
    new_q = _quantiles(traffic["max_new"], block)
    plens = np.concatenate([order.permutation(plen_q)
                            for _ in range(n_blocks)])[:n]
    news = np.concatenate([order.permutation(new_q)
                           for _ in range(n_blocks)])[:n]
    arrival = traffic["arrival"]
    kind = arrival["kind"]
    if kind == "backlog":
        due = np.zeros(n)
    elif kind in ("poisson", "bursty"):
        gap_q = _gaps(arrival, block)
        gaps = np.concatenate([order.permutation(gap_q)
                               for _ in range(n_blocks)])[:n]
        due = np.cumsum(gaps) - gaps[0]
        if kind == "bursty":
            # arrivals live in ON time; every completed ON window
            # pushes what follows past one OFF window
            on_s, off_s = float(arrival["on_s"]), float(arrival["off_s"])
            due = due + np.floor(due / on_s) * off_s
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    max_total = int(traffic.get("max_total", 0))
    sessions = traffic.get("sessions")
    streams: dict[int, np.ndarray] = {}
    turns: dict[int, int] = {}
    base: dict[int, int] = {}
    out = []
    for i in range(n):
        plen, new = int(plens[i]), int(news[i])
        if sessions:
            s = i % int(sessions["count"])
            t = turns.get(s, 0)
            turns[s] = t + 1
            base.setdefault(s, plen)
            plen = base[s] + t * int(sessions.get("grow", 4))
            if max_total:
                plen = min(plen, max_total - new)
            if s not in streams:
                streams[s] = rng.integers(0, vocab, size=max_total or 4096)
            prompt = streams[s][:plen]
        else:
            if max_total and plen + new > max_total:
                plen = max_total - new
            prompt = rng.integers(0, vocab, size=plen)
        out.append({"due_s": float(due[i]), "prompt": prompt.tolist(),
                    "max_new": new})
    return out


def describe(traffic: dict) -> dict:
    """Mean/median/cap share of one block — printed by every run so the
    mix a number was measured on is on its record."""
    block = int(traffic.get("block", 128))
    p = _quantiles(traffic["prompt_len"], block)
    m = _quantiles(traffic["max_new"], block)
    return {"block": block, "prompt_mean": float(p.mean()),
            "prompt_median": float(np.median(p)),
            "prompt_at_cap_share": float(np.mean(p == p.max())),
            "max_new_mean": float(m.mean()),
            "tokens_per_block": int(p.sum() + m.sum())}
