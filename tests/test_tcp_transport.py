"""The multi-host TCP transport and async live migration (DESIGN.md
section 28): worker processes behind a length-prefixed TCP loopback
protocol, a reconnect ladder that converts dropped connections into
sequence-numbered replays instead of dead-host declarations, and the
three network chaos kinds a same-host socket cannot drill —
``partition_worker`` (link down both ways, heal, reconnect-and-replay),
``slow_link`` (injected per-call latency that must NOT page the
liveness ladder), ``drop_conn`` (mid-message RST: reconnect, no
duplicate side effects, no lost response).

Every fleet test here spawns worker subprocesses (jax import + engine
build per worker), so the module is ``serial``-marked and deadlines
are load-scaled; the idempotency-audit and replay-verdict tests at the
top are pure table checks and run in microseconds. The model/config
shapes are the shared test fixtures (V=64, D=32, L=2, H=4, BASE
blocks) so every compiled program hits the persistent XLA cache.
"""

import contextlib
import io
import inspect
import os
import re
import time

import jax
import numpy as np
import pytest

from conftest import load_scaled_timeout
from distributed_llm_code_samples_tpu.decode import (DecodeEngine,
                                                     EngineConfig,
                                                     FleetRouter)
from distributed_llm_code_samples_tpu.decode import worker as worker_mod
from distributed_llm_code_samples_tpu.decode.worker import (
    IDEMPOTENT_OPS, NON_IDEMPOTENT_OPS, WORKER_OPS, replay_verdict,
    spawn_fleet_handles, spawn_worker)
from distributed_llm_code_samples_tpu.models import init_lm
from distributed_llm_code_samples_tpu.runtime.chaos import (
    FaultPlan, validate_fleet_plan)
from distributed_llm_code_samples_tpu.runtime.telemetry import (
    METRICS_FILENAME, SCHEMA_VERSION, TelemetryWriter, read_metrics,
    validate_record)

pytestmark = pytest.mark.serial

V, D, L, H = 64, 32, 2, 4
BASE = dict(block_size=8, n_blocks=33, max_slots=3,
            max_blocks_per_seq=6, prefill_chunk=8)
MODEL = dict(vocab=V, model_size=D, layers=L, heads=H, kv_heads=None,
             max_seq_len=64, random_seed=0)
MAX_NEW = 8


@pytest.fixture(scope="module")
def lm_params():
    return init_lm(jax.random.PRNGKey(0), V, D, L, max_seq_len=64)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, V, size=n).tolist() for n in (5, 9, 13)]


def _oracle(lm_params, prompts, **cfg_extra):
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE, **cfg_extra))
    for p in prompts:
        eng.submit(p, MAX_NEW)
    return eng.run()


def _spawn(n, base_dir, metrics_root=None, **cfg_extra):
    deadline = load_scaled_timeout(120.0)
    return spawn_fleet_handles(
        n, 0, str(base_dir), model=MODEL,
        config={**BASE, **cfg_extra}, policy={}, family="tcp",
        metrics_root=metrics_root,
        call_deadline_s=deadline, connect_deadline_s=deadline)


# ---------------------------------------------------------------------------
# the idempotency audit: every worker op is classified, the table covers
# exactly the dispatch, and the replay verdict honors the classes


def test_worker_ops_table_covers_dispatch():
    """The op tables ARE the replay-safety contract, so they must
    cover the dispatch exactly: every ``op == "..."`` branch in the
    worker's handler appears in exactly one of IDEMPOTENT_OPS /
    NON_IDEMPOTENT_OPS, and nothing is classified that the worker
    does not serve — an op added to the dispatch without a replay
    classification fails HERE, not in a partition drill."""
    assert not (IDEMPOTENT_OPS & NON_IDEMPOTENT_OPS)
    src = inspect.getsource(worker_mod.worker_main)
    dispatched = set(re.findall(r'op == "(\w+)"', src))
    assert dispatched == set(WORKER_OPS), (
        "dispatch/table drift: "
        f"unclassified={sorted(dispatched - set(WORKER_OPS))} "
        f"unserved={sorted(set(WORKER_OPS) - dispatched)}")


def test_replay_verdict_per_op():
    """The router-side replay decision, per op class, against a synced
    worker dedup state (horizon=highest evicted id, cached=ids still
    held): a cached id replays from cache for ANY op; an id past the
    horizon provably never ran, so any op resends; an id at-or-below
    the horizon resends only if idempotent — a non-idempotent op whose
    response fell off the window is REFUSED (it may have executed, and
    a duplicate side effect is worse than a dead-host declaration)."""
    horizon, cached = 10, {11, 12}
    for op in WORKER_OPS:
        assert replay_verdict(op, 11, horizon, cached) == "cached", op
        assert replay_verdict(op, 13, horizon, cached) == "resend", op
    for op in IDEMPOTENT_OPS:
        assert replay_verdict(op, 9, horizon, cached) == "resend", op
    for op in NON_IDEMPOTENT_OPS:
        assert replay_verdict(op, 9, horizon, cached) == "refuse", op


# ---------------------------------------------------------------------------
# the async-migration engine contract (no workers: the delta catch-up
# math must hold before any socket is involved)


def test_engine_async_export_catchup(lm_params, prompts):
    """``export_sequence(keep=True)`` leaves the source decoding; the
    tokens it emits during the ship window come back from
    ``finish_export`` as the full list, and importing the shipped doc
    with the PATCHED out (emitted pinned at the ship point) teacher-
    forces the delta on the target — byte-identical completion, and
    the catch-up is real (> 0 tokens emitted mid-ship)."""
    want = _oracle(lm_params, prompts[:1])
    e1 = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    e1.submit(prompts[0], MAX_NEW, uid=0)
    for _ in range(3):                   # prefill + a few tokens
        e1.step()
    doc = e1.export_sequence(0, keep=True)
    shipped = int(doc["emitted"])
    for _ in range(2):                   # the ship window: source
        e1.step()                        # KEEPS decoding
    delta = e1.finish_export(0)
    assert delta["status"] == "resident"
    assert len(delta["out"]) > shipped   # catch-up is non-empty
    assert doc["out"] == delta["out"][:shipped]   # strict prefix
    e2 = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    e2.import_sequence({**doc, "out": delta["out"]})
    got = e2.run()
    assert got[0] == want[0]
    # the source really evicted at commit, not at export
    assert all(s is None or s.uid != 0 for s in e1.slots)


# ---------------------------------------------------------------------------
# the network chaos drills (real worker processes, TCP loopback)


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_tcp_kill_one_of_three_partition_heal(lm_params, prompts,
                                              tmp_path, kv_dtype):
    """THE acceptance drill over TCP loopback: partition one worker's
    link mid-stream (partition_worker@4:2 — both ways, heals), then
    SIGKILL another (kill_worker@8:1), with async migration on. Every
    request completes token-identically vs the in-process oracle at
    f32 AND int8, the partition costs a reconnect-and-replay and ZERO
    dead-host declarations (kills == the one scheduled SIGKILL), and
    the heal is visible as a schema-v16 ``reconnected`` router
    record."""
    want = _oracle(lm_params, prompts, kv_dtype=kv_dtype)
    plan = FaultPlan.parse("partition_worker@4:2,kill_worker@8:1")
    validate_fleet_plan(plan)
    rm = TelemetryWriter(str(tmp_path / "router"),
                         meta={"engine_id": "router"})
    handles = _spawn(3, tmp_path / "spool", kv_dtype=kv_dtype)
    fl = FleetRouter(None, 3, handles=handles, metrics=rm,
                     fleet_chaos=plan, async_migration=True)
    try:
        for p in prompts:
            fl.submit(p, MAX_NEW)
        out = fl.run()
    finally:
        fl.close()
        rm.close()
    assert out == want and not fl.failed()
    assert fl.kills == 1                 # the scheduled SIGKILL only
    assert fl.reconnects_total >= 1      # the partition healed
    records, problems = read_metrics(
        os.path.join(str(tmp_path / "router"), METRICS_FILENAME))
    assert not problems, problems
    # zero transport deaths: the partition never became a declaration
    assert not [r for r in records
                if r.get("event") == "worker_dead"]
    recon = [r for r in records if r["kind"] == "router"
             and r["event"] == "reconnected"]
    assert recon, "the heal left no reconnected record"
    for r in recon:
        ok, reason = validate_record(r)
        assert ok, reason
        assert r["schema"] == SCHEMA_VERSION
        assert r["attempts"] >= 1 and r["uid"] == -1
        assert r["replayed_ops"] >= 0
    for r in [r for r in records if r["kind"] == "router"
              and r["event"] == "migrated"]:
        ok, reason = validate_record(r)
        assert ok, reason


def test_tcp_drop_conn_exactly_once(lm_params, prompts, tmp_path):
    """A mid-message RST (drop_conn@3: the worker tears the socket
    right after queueing its response): the router reconnects, the
    sync handshake hands it the worker's dedup state, and the replay
    answers from the response cache — no duplicate side effect, no
    lost response, zero kills, and the tokens still match the oracle.
    The live status doc names the family and the reconnect count."""
    want = _oracle(lm_params, prompts)
    plan = FaultPlan.parse("drop_conn@3")
    validate_fleet_plan(plan)
    handles = _spawn(2, tmp_path / "spool")
    fl = FleetRouter(None, 2, handles=handles, fleet_chaos=plan)
    try:
        for p in prompts:
            fl.submit(p, MAX_NEW)
        out = fl.run()
        st = fl.status_doc()
    finally:
        fl.close()
    assert out == want and not fl.failed()
    assert fl.kills == 0 and fl.reconnects_total >= 1
    assert st["counters"]["reconnects"] == fl.reconnects_total
    fams = {e["family"] for e in st["engines"].values()
            if e.get("alive")}
    assert fams == {"tcp"}
    assert sum(e.get("reconnects", 0)
               for e in st["engines"].values()
               if e.get("alive")) == fl.reconnects_total


def test_tcp_slow_link_below_deadline_not_paged(lm_params, prompts,
                                                tmp_path):
    """Injected per-call latency below the deadline (slow_link@3:40)
    is a SLOW link, not a dead host: the liveness ladder must not
    page — zero kills, zero reconnects, tokens identical. This is the
    boundary the per-call deadline exists to draw."""
    want = _oracle(lm_params, prompts)
    plan = FaultPlan.parse("slow_link@3:40")
    validate_fleet_plan(plan)
    handles = _spawn(2, tmp_path / "spool")
    fl = FleetRouter(None, 2, handles=handles, fleet_chaos=plan)
    try:
        for p in prompts:
            fl.submit(p, MAX_NEW)
        out = fl.run()
    finally:
        fl.close()
    assert out == want and not fl.failed()
    assert fl.kills == 0 and fl.reconnects_total == 0


def test_tcp_async_pool_pressure_migration(lm_params, tmp_path):
    """The async live-migration pipeline end-to-end over TCP: a
    block-starved worker's youngest running sequence ships WHILE the
    source keeps decoding (export_keep -> fetch_wire -> stage_bytes),
    and the commit patches the delta — the migrated record carries
    transport mode "tcp", a real ship window (``ship_s``), and a
    non-zero catch-up, with the commit stall (``duration_s``) a
    fraction of the window the ship overlapped. Tokens byte-identical
    to the single-engine oracle."""
    rng = np.random.default_rng(1)
    prompts4 = [rng.integers(0, V, size=n).tolist()
                for n in (5, 9, 13, 11)]
    eng = DecodeEngine(lm_params, H, EngineConfig(**BASE))
    for p in prompts4:
        eng.submit(p, MAX_NEW)
    want = eng.run()
    deadline = load_scaled_timeout(120.0)
    rm = TelemetryWriter(str(tmp_path / "router"),
                         meta={"engine_id": "router"})
    # per-worker configs: e0 block-starved (6 blocks), e1 roomy — all
    # admissions pin to e0, pool pressure triggers the move
    h0 = spawn_worker("e0", "decode", str(tmp_path / "spool"),
                      model=MODEL, config={**BASE, "n_blocks": 6},
                      policy={}, family="tcp",
                      call_deadline_s=deadline,
                      connect_deadline_s=deadline)
    h1 = spawn_worker("e1", "decode", str(tmp_path / "spool"),
                      model=MODEL, config=BASE, policy={},
                      family="tcp", call_deadline_s=deadline,
                      connect_deadline_s=deadline)
    fl = FleetRouter(None, 2, handles=[h0, h1], metrics=rm,
                     async_migration=True)
    try:
        for p in prompts4:
            fl.submit(p, MAX_NEW, session="pin")
        out = fl.run()
    finally:
        fl.close()
        rm.close()
    assert out == want and not fl.failed()
    assert fl.migrations >= 1
    records, problems = read_metrics(
        os.path.join(str(tmp_path / "router"), METRICS_FILENAME))
    assert not problems, problems
    migs = [r for r in records if r["kind"] == "router"
            and r["event"] == "migrated"
            and r["reason"] == "pool_pressure"]
    assert migs, "pool pressure never migrated"
    for r in migs:
        ok, reason = validate_record(r)
        assert ok, reason
        assert r["transport"]["mode"] == "tcp"
        assert r["bytes"] > 0
        assert r["ship_s"] is not None and r["ship_s"] > 0
        assert r["catchup_tokens"] >= 1      # source decoded mid-ship
        # the request paid a commit stall, never a ship-long source
        # stall: the overlapped window dwarfs the synchronous part
        assert r["duration_s"] < r["ship_s"]


# ---------------------------------------------------------------------------
# CLI parse-rejection discipline (no engine is ever built)


def test_tcp_cli_spec_rejections():
    """Malformed --transport/--fleet_chaos combinations reject rc 2
    with ONE stderr line before any engine build: the network kinds
    need --transport tcp (partition/drop drill the reconnect ladder;
    slow_link needs a socket to slow), and malformed args reject in
    parse."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    shape = ["--prompt_lens", "4", "--max_new", "2", "-d", "32",
             "-l", "2", "--heads", "4", "--vocab", "64",
             "--max_seq_len", "64", "--block_size", "8"]
    for bad in (
        # network chaos without the TCP transport
        ["--fleet", "2", "--fleet_chaos", "partition_worker@2"],
        ["--fleet", "2", "--transport", "process",
         "--fleet_chaos", "partition_worker@2"],
        ["--fleet", "2", "--transport", "process",
         "--fleet_chaos", "drop_conn@2"],
        ["--fleet", "2", "--fleet_chaos", "slow_link@2:40"],
        # malformed args
        ["--fleet", "2", "--transport", "tcp",
         "--fleet_chaos", "partition_worker@2:-1"],
        ["--fleet", "2", "--transport", "tcp",
         "--fleet_chaos", "slow_link@2:-5"],
        ["--fleet", "2", "--transport", "tcp",
         "--fleet_chaos", "drop_conn@2:9"],
        # fleet-only flags without a fleet
        ["--transport", "tcp"],
        ["--async_migration"],
    ):
        err = io.StringIO()
        with contextlib.redirect_stderr(err), \
                contextlib.redirect_stdout(io.StringIO()):
            rc = generate_main(bad + shape)
        assert rc == 2, (bad, err.getvalue())
        msg = err.getvalue().strip()
        assert msg and len(msg.splitlines()) == 1, (bad, msg)


def test_worker_transports_refuse_a_tpu_parent(monkeypatch):
    """One chip, one process: on a TPU backend the router process holds
    the chip, so --transport process|tcp (a worker process per engine)
    must fail at start, rc 2, with one message — not hang waiting for
    a device the children can never get. inproc is not refused."""
    from distributed_llm_code_samples_tpu.decode.generate_cli import (
        generate_main)
    from distributed_llm_code_samples_tpu.runtime import init
    monkeypatch.setattr(init, "describe_devices", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    shape = ["--prompt_lens", "4", "--max_new", "2", "-d", "32",
             "-l", "2", "--heads", "4", "--vocab", "64",
             "--max_seq_len", "64", "--block_size", "8", "--fleet", "2"]
    for transport in ("process", "tcp"):
        err = io.StringIO()
        with contextlib.redirect_stderr(err), \
                contextlib.redirect_stdout(io.StringIO()):
            rc = generate_main(shape + ["--transport", transport])
        assert rc == 2
        msg = err.getvalue().strip()
        assert len(msg.splitlines()) == 1 and "holds the chip" in msg, msg
    with contextlib.redirect_stderr(io.StringIO()), \
            contextlib.redirect_stdout(io.StringIO()):
        assert generate_main(shape + ["--transport", "inproc"]) == 0
