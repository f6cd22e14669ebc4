"""High-throughput decode engine: paged KV cache, continuous batching,
quantized KV, fused sampling (``decode/engine.py``, DESIGN.md section
15) — plus the round-10 serving reliability layer: in-graph logits
quarantine, pool-pressure preemption, snapshot-resume supervision, and
request-level admission control (``decode/supervise.py``, DESIGN.md
section 16)."""

from .draft import draft_tokens
from .engine import (AdmissionError, DecodeEngine, EngineConfig,
                     FLIGHT_FILENAME, HANDOFF_VERSION, POISON_ALL,
                     POISON_NONE, REQUEST_EVENTS, ServePolicy)
from .fleet import (EngineHandle, FleetRouter, HandoffRef,
                    TransportDead, TransportError, TransportTimeout)
from .paged import (KV_DTYPES, PagedKV, SCRATCH_BLOCK, copy_block,
                    corrupt_block, extract_blocks,
                    gather_layer, implant_block, init_pool,
                    kv_bytes_per_token, pool_bytes, scrub_blocks,
                    stored_decode_attn, write_chunk, write_rows)
from .prefix import PrefixCache, PrefixNode
from .sampling import check_sampling, check_speculation, make_pick
from .supervise import (SNAPSHOT_FILENAME, load_snapshot,
                        restore_engine_state, snapshot_state,
                        supervise_decode, write_snapshot)
from .worker import (ProcessEngineHandle, spawn_fleet_handles,
                     spawn_worker)

__all__ = [
    "AdmissionError", "DecodeEngine", "EngineConfig", "EngineHandle",
    "FLIGHT_FILENAME", "FleetRouter", "HANDOFF_VERSION", "HandoffRef",
    "ProcessEngineHandle", "TransportDead", "TransportError",
    "TransportTimeout", "spawn_fleet_handles", "spawn_worker",
    "POISON_ALL", "POISON_NONE", "REQUEST_EVENTS", "ServePolicy",
    "KV_DTYPES", "PagedKV", "SCRATCH_BLOCK", "copy_block",
    "corrupt_block", "draft_tokens", "extract_blocks",
    "gather_layer", "implant_block", "init_pool",
    "kv_bytes_per_token", "pool_bytes",
    "PrefixCache", "PrefixNode",
    "scrub_blocks", "stored_decode_attn", "write_chunk", "write_rows",
    "check_sampling", "check_speculation", "make_pick",
    "SNAPSHOT_FILENAME", "load_snapshot", "restore_engine_state",
    "snapshot_state", "supervise_decode", "write_snapshot",
]
