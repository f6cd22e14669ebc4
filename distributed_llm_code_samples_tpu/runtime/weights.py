"""Weight lifecycle: the version ledger + model fingerprint both the
trainer's checkpoint dir and the serving engines read.

Before round 17 the trainer and the engines kept twins of every weight
fact: the checkpoint layer owned publish/verify (atomic fsync + CRC,
``checkpoint.py``), while the serving side computed its own coarse
model fingerprint in THREE call sites (engine ``model_meta``, the
supervise snapshot, the handoff doc) and had no notion of "which
weights" at all — publishing a new checkpoint into a running fleet
meant a restart. This module is the one home ROADMAP item 3 demanded:

- **``model_fingerprint``** — THE fingerprint (shapes + the coarse
  embedding-row sum that catches a changed init at the same shape).
  ``DecodeEngine.model_meta`` re-binds to it (the ``wire.py``
  re-binding pattern from round 16), so snapshot-resume, the KV
  handoff, and the version ledger can never drift on what "the same
  model" means. ``same_architecture`` splits the shape keys from the
  value fingerprint: two VERSIONS of one model share every key except
  ``wte0_sum``.

- **``VersionLedger``** — the version ledger over an existing
  checkpoint directory. A weights VERSION is simply a published
  checkpoint step (``step_{N}/``): ``latest_step`` is the newest
  publish (what a deploy targets), ``latest_verified`` the newest step
  that passes the CRC ladder (what a failed deploy falls back to —
  ``checkpoint.latest_verified_step``, verbatim), ``verify`` the
  per-step integrity check, and ``load`` restores a step into an
  architecture template (the engine's own params tree) with the
  fresh-ownership device_put ``restore_checkpoint`` already performs.
  Publish-for-serving is deliberately NOT re-implemented: the
  trainer's existing atomic publish IS the deploy input.

Version id conventions: ``BOOT_VERSION`` (0) names the weights an
engine was CONSTRUCTED with; deployed versions carry their checkpoint
step. The serving side's pin/swap machinery (double-buffered engine
weights, per-request ``weights_version`` pins, the fleet's rolling
deploy) lives with the engine and router (``decode/engine.py``,
``decode/fleet.py``, DESIGN.md section 23) — this module owns only
what trainer and server must AGREE on: identity and the ladder.
"""

from __future__ import annotations

import os

# the version id of the weights an engine was constructed with (a
# deployed version's id is its checkpoint step — trainer steps are
# 1-based for real publishes, and a step_0 deploy to a just-booted
# engine is a no-op by fingerprint equality)
BOOT_VERSION = 0

# the fingerprint key that carries VALUE identity (init seed / training
# progress); every other model_fingerprint key is architecture
VALUE_KEYS = ("wte0_sum",)


def model_fingerprint(params, n_heads: int) -> dict:
    """Model identity snapshots, KV handoffs, and the version ledger
    all pin — THE one definition (the engine/snapshot/handoff call
    sites re-bind to it). Shapes catch a changed architecture; the
    embedding-row fingerprint catches a changed init seed (or a
    different training step) at the same shape — rounded coarsely so
    the float reduction order, which legitimately varies across TP
    layouts, can't cause a false mismatch."""
    import jax.numpy as jnp
    return {
        "vocab": int(params.vocab),
        "d_model": int(params.d_model),
        "n_layers": int(params.n_layers),
        "max_seq_len": int(params.max_seq_len),
        "n_heads": int(n_heads),
        "kv_heads": int(params.cache_spec(int(n_heads)).kv_heads),
        "wte0_sum": round(float(jnp.sum(params.wte[0])), 2),
    }


def same_architecture(a: dict, b: dict) -> bool:
    """True when two fingerprints describe the same MODEL SHAPE —
    every key except the value fingerprint matches. Two versions of
    one model are same-architecture with different ``wte0_sum``; a
    hot-swap between different architectures is never legal (the KV
    pool layout and the compiled program set are shape functions)."""
    keys = (set(a) | set(b)) - set(VALUE_KEYS)
    return all(a.get(k) == b.get(k) for k in keys)


def architecture_diff(a: dict, b: dict) -> dict:
    """The mismatching architecture keys (for one-line error text)."""
    keys = (set(a) | set(b)) - set(VALUE_KEYS)
    return {k: (a.get(k), b.get(k)) for k in sorted(keys)
            if a.get(k) != b.get(k)}


class VersionLedger:
    """The weight-version view of one trainer checkpoint directory.

    Thin by design: every integrity rule is the checkpoint layer's
    (per-file CRC-32, ``latest_verified_step`` fallback) — the ledger
    adds only the serving-side vocabulary (versions, targets,
    fallbacks) and the fingerprint cache a router consults when it
    records a deploy. Imports are lazy so the jax-free callers
    (``report``, the worker transport client) can import this module
    without paying the checkpoint layer's jax import."""

    def __init__(self, ckpt_dir: str):
        self.ckpt_dir = ckpt_dir
        self._fingerprints: dict[int, dict] = {}

    def step_path(self, step: int) -> str:
        return os.path.join(self.ckpt_dir, f"step_{int(step)}")

    def latest_step(self) -> int | None:
        """Newest PUBLISHED step (unverified) — what a deploy with no
        explicit step targets; the CRC ladder then accepts or rejects
        it."""
        from ..checkpoint import latest_step
        return latest_step(self.ckpt_dir)

    def latest_verified(self) -> int | None:
        """Newest step that passes the CRC ladder — the rollback
        anchor a rejected deploy names."""
        from ..checkpoint import latest_verified_step
        return latest_verified_step(self.ckpt_dir)

    def verify(self, step: int) -> tuple[bool, str]:
        """Integrity-check one step (``checkpoint.verify_checkpoint``
        — meta parses, every payload CRC matches). The reason string
        is ONE line: it becomes the deploy record's named rollback
        reason verbatim."""
        from ..checkpoint import verify_checkpoint
        path = self.step_path(step)
        if not os.path.isdir(path):
            return False, f"step_{int(step)} not published"
        return verify_checkpoint(path)

    def load(self, step: int, template):
        """Restore step ``step`` into ``template``'s tree (the
        engine's own params — same architecture or the restore's
        shape/dtype checks reject it). Integrity-verified; raises
        ``checkpoint.CorruptCheckpointError`` with the one-line
        reason on a torn/bit-flipped step. Leaves arrive as FRESH
        exclusively-owned device buffers (``restore_checkpoint``'s
        jitted-copy ownership contract) — the swap's one device_put."""
        from ..checkpoint import restore_checkpoint
        params, got_step, _ = restore_checkpoint(self.ckpt_dir, template,
                                                 step=int(step))
        assert got_step == int(step)
        return params

    def fingerprint(self, step: int, params, n_heads: int) -> dict:
        """Fingerprint of a loaded version, cached per step (the
        router records it on every deploy event for the step)."""
        fp = self._fingerprints.get(int(step))
        if fp is None:
            fp = model_fingerprint(params, n_heads)
            self._fingerprints[int(step)] = fp
        return fp
