"""Runtime layer: process bootstrap + native C++ components + failure
supervision.

The TPU-native replacement for the reference's L0/L4 runtime surface
(SURVEY.md): ``init`` wraps the multi-host bootstrap
(``jax.distributed``); ``native`` binds the in-tree C++ engines (host ring
collectives, prefetching data loader, TCP rendezvous/barrier with timeout,
watchdog, XLA FFI custom calls); ``failure`` adds hang/peer/device failure
detection and checkpoint-based elastic recovery; ``chaos`` injects
deterministic faults so that story is continuously tested; ``telemetry``
is the unified metrics stream (schema-versioned per-step JSONL records +
the ``StepReport`` static fold) every run/bench/report shares;
``tracing`` is the span layer on top of it: per-request lifecycle spans
(the serving waterfall's telescoping clock) and per-step host phases
(``PhaseTimer``, the package's profiler annotations).
"""

from . import chaos, native, telemetry, tracing, weights
from .chaos import FaultPlan
from .failure import (HealthCheckError, device_healthcheck, supervise)
from .init import (DEFAULT_COORDINATOR, describe_devices,
                   enable_compile_cache, initialize, runtime_info)
from .telemetry import StepReport, TelemetryWriter
from .tracing import PhaseTimer, SpanTracer
from .weights import VersionLedger, model_fingerprint

__all__ = ["chaos", "native", "telemetry", "tracing",
           "weights", "describe_devices", "enable_compile_cache",
           "initialize", "runtime_info",
           "DEFAULT_COORDINATOR", "FaultPlan", "HealthCheckError",
           "device_healthcheck", "supervise", "StepReport",
           "TelemetryWriter", "PhaseTimer", "SpanTracer", "VersionLedger",
           "model_fingerprint"]
