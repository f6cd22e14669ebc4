"""What every cell's run shares: finding the cell's files, refusing to
run off the chip, counting compilations, the traced window, the
per-layer readers and the one result line."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ANNOTATION = "bench:"            # prefix of every host span we record
WINDOW = ANNOTATION + "traced_window"


def read_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's workload and configuration files, found by name, and
    the metrics ``BENCHMARK.json`` lists for it."""
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name):
        raise SystemExit(f"bad workload name {name!r}")
    path = os.path.join(HERE, "workloads", name + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"no workload file {path}")
    work = read_json("workloads", name + ".json")
    config = read_json("configs", work["config"] + ".json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json lists no workload {name!r}")
    if entry["config"] != work["config"] or entry["chips"] != work["chips"]:
        raise SystemExit(f"{path} and BENCHMARK.json disagree on the "
                         "cell's configuration or chips")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    e2e = mine(bench["end_to_end"])
    reported = {m["name"] for m in e2e}
    layer = [m for m in mine(bench["per_layer"]) if m["moves"] in reported]
    return {"name": name, "work": work, "config": config,
            "config_name": work["config"], "chips": work["chips"],
            "end_to_end": e2e, "per_layer": layer}


def _module_from(*parts: str):
    """A module from a file under the benchmark's directory, by path
    (configurations and readers are found by name, not imported)."""
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", "_".join(parts)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The configuration's plain reference, from the file beside it."""
    return _module_from("configs", config["reference"] + ".py")


def driver_module(config: dict):
    """How the program is reached for this configuration (its engine or
    its trainer), from the file beside it."""
    return _module_from("configs", config["driver"] + ".py")


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; exits where it is no TPU or there
    are fewer chips than the cell asks for."""
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}
    print(f"devices: {info}", file=sys.stderr)
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark: JAX's first device is "
                         f"{info['platform']!r}, not a TPU: nothing run")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                         f"JAX sees {info['count']}")
    return info


class Compiles:
    """Compile seconds and persistent-cache traffic from JAX's own
    monitoring events (``chip_smoke.py::_Compiles``, copied)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def programs(self) -> int:
        """Programs built or fetched so far: inside a measured window
        this may not move."""
        return self.compiles + self.hits + self.misses


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(ANNOTATION + name)


class Tracer:
    """The traced part of a ``--trace 1`` window: starts the profiler
    ``after_s`` into the window, stops it ``for_s`` later, then reduces
    the trace. Off (``--trace 0``) every method is a no-op."""

    def __init__(self, on: bool, after_s: float, for_s: float):
        self.on, self.after_s, self.for_s = on, after_s, for_s
        self.dir = os.path.join(ROOT, ".bench_trace")
        self.state = "idle" if on else "done"
        self._annot = None
        self.t_start = None

    def poll(self, t_in_window: float) -> None:
        import jax
        if self.state == "idle" and t_in_window >= self.after_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self._annot = jax.profiler.TraceAnnotation(WINDOW)
            self._annot.__enter__()
            self.t_start = time.perf_counter()
            self.state = "tracing"
        elif (self.state == "tracing"
              and time.perf_counter() - self.t_start >= self.for_s):
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state != "tracing":
            return
        self._annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self) -> dict | None:
        """``{"trace", "lo", "hi", "busy_s", "window_s"}`` or None."""
        if not self.on or self.t_start is None:
            return None
        from . import xplane
        path = xplane.find_xplane(self.dir)
        trace = xplane.load(path, host_names=(ANNOTATION,))
        lo, hi = xplane.window(trace, WINDOW)
        per_dev = xplane.busy(trace, lo, hi)
        busy = (sum(d["busy_s"] for d in per_dev.values())
                / max(len(per_dev), 1))
        shutil.rmtree(self.dir, ignore_errors=True)
        return {"trace": trace, "lo": lo, "hi": hi, "busy_s": busy,
                "window_s": (hi - lo) / 1e9}


def breakdown(red: dict) -> dict:
    from . import xplane
    ops = xplane.op_seconds(red["trace"], red["lo"], red["hi"])
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])[:10]
    return {"device_ops": [[k, v[0]] for k, v in top],
            "idle_gaps": xplane.idle_gaps(red["trace"], red["lo"],
                                          red["hi"], ANNOTATION)}


# -- per-layer readers ---------------------------------------------------


def _device_time(spec: dict, ctx: dict):
    """Device seconds of the ops (or programs) whose scope or name
    matches ``pattern``, divided as ``per`` says."""
    from . import xplane
    red = ctx.get("trace")
    if red is None:
        return None
    pat = re.compile(spec["pattern"])
    line = spec.get("line", xplane.OPS_LINE)
    got = xplane.op_seconds(
        red["trace"], red["lo"], red["hi"], line=line,
        key=lambda n, s: "x" if pat.search(s or n) else None).get("x")
    if not got:
        return None
    seconds, count = got
    n_dev = max(len(xplane.device_planes(red["trace"])), 1)
    per = spec.get("per", "total")
    if per == "event":
        return seconds / count * spec.get("scale", 1.0)
    if per == "total":
        return seconds / n_dev * spec.get("scale", 1.0)
    denom = ctx["values"].get(per)       # a counter of the traced window
    if not denom:
        return None
    return seconds / n_dev / denom * spec.get("scale", 1.0)


def _span_quantile(spec: dict, ctx: dict):
    durs = [s["duration_s"] for s in ctx.get("spans", [])
            if s.get("span") == spec["span"]]
    if not durs:
        return None
    return float(np.quantile(durs, spec["q"])) * spec.get("scale", 1.0)


def read_layer_metric(name: str, ctx: dict):
    """The metric's value by its reader file, or None where the reader
    finds nothing to read."""
    spec = read_json("layer_metrics", name + ".json")
    reader = spec["reader"]
    kind = reader["kind"]
    if kind == "value":
        return ctx["values"].get(reader["key"])
    if kind == "span_quantile":
        return _span_quantile(reader, ctx)
    if kind == "device_time":
        return _device_time(reader, ctx)
    if kind == "engine_program":
        from . import engine_trace
        return engine_trace.program_ms(ctx, reader["program"])
    if kind == "python":
        return _module_from("layer_metrics", name + ".py").read(ctx)
    raise ValueError(f"layer metric {name}: unknown reader kind {kind!r}")


def peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def result_line(cell: dict, device: dict, trace_on: bool, outcome: dict,
                ctx: dict) -> dict:
    """The contract's last line. ``outcome``: correct, attempted,
    failed, and ``e2e`` values by metric name."""
    metrics = {}
    if not trace_on:
        for m in cell["end_to_end"]:
            val = outcome["e2e"].get(m["name"])
            if val is None:
                raise RuntimeError(f"end-to-end metric {m['name']} was "
                                   "not measured")
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        for m in cell["per_layer"]:
            val = read_layer_metric(m["name"], ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=ctx["values"]["memory_peak_bytes"])
    line = {"correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]), "metrics": metrics,
            "device": dev}
    red = ctx.get("trace")
    if trace_on and red is not None:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        line["breakdown"] = breakdown(red)
    return line


_T0 = time.perf_counter()


def note(msg: str) -> None:
    """A progress line on stderr, stamped with seconds since import."""
    print(f"[bench {time.perf_counter() - _T0:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def say(**kw) -> None:
    """One earlier stdout line (never the last)."""
    print(json.dumps(kw), flush=True)
