"""A CPU rehearsal of every cell's control flow at tiny size, the
planted faults that ``correct`` has to catch, the lower-precision
control at a size a test run holds, and the refusal to run off a TPU.
Nothing here is a measurement."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import flops, run
from benchmark.tests import shrink

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
SEED = 2**31 + 4242          # the driver's seeds pass 32 signed bits


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    """The CPU has no row in peaks.json (and must not get one)."""
    real = flops.peaks
    monkeypatch.setattr(flops, "peaks",
                        lambda kind: real("TPU v5 lite"))


def rehearse(name, trace, **kw):
    return run.run_cell(name, SEED, 1.5, bool(trace), check_device=False,
                        shrink=shrink.for_cell(name), **kw)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(name, trace):
    line = rehearse(name, trace)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    listed = {m["name"]: m for m in
              BENCH["per_layer" if trace else "end_to_end"]}
    for metric, body in line["metrics"].items():
        assert body["unit"] == listed[metric]["unit"]
        assert body["value"] == body["value"]       # not NaN
    if not trace:
        wanted = {m["name"] for m in BENCH["end_to_end"]
                  if name in m.get("workloads", [name])}
        assert set(line["metrics"]) == wanted
    else:
        assert line["metrics"], "a traced run reports per-layer metrics"
        assert {"busy_s", "window_s"} <= set(line["device"])


def test_open_loop_kind_runs(capsys):
    """``serve-open`` (arrivals on the clock, timed from due time, a
    drain) has no cell yet; its control flow must not rot."""
    name = next(c for c in CELLS if "gpt2" in c)
    line = run.run_cell(name, SEED, 1.5, False, check_device=False,
                        shrink=shrink.serve_open)
    assert line["correct"] is True and line["attempted"] > 0
    lat = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    lat = next(r for r in lat if r.get("phase") == "latency")
    assert lat["ttft_p90_ms"] > 0 and lat["itl_p99_ms"] > 0
    assert lat["generator_late_max_ms"] >= 0


@pytest.mark.parametrize("name", [c for c in CELLS if "gpt2" in c][:1])
def test_an_altered_token_is_not_correct(name):
    """The timed path broken where a token is produced: every served
    token shifted by one id."""
    def alter(full, plen):
        return full[:plen] + [(t + 1) % 64 for t in full[plen:]]
    assert rehearse(name, 0, alter=alter)["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    name = next(c for c in CELLS if c.startswith("ffn"))
    line = rehearse(name, 0, alter=lambda before, after: before)
    assert line["correct"] is False


@pytest.mark.parametrize("name", [c for c in CELLS if "gpt2" in c][:1])
def test_lower_precision_control_reads_wider_than_the_program(name, capsys):
    """At tiny size on the CPU the program is exact against the
    reference (gap 0); the int8 control is not. The limits themselves
    are set from chip runs at the cell's own size (PERF.md)."""
    def many(cell):
        shrink.for_cell(name)(cell)
        # enough near-ties for int8 to break some: more tokens, and a
        # vocabulary wide enough that top logits lie close
        cell["config"].update(vocab_size=2048)
        cell["work"]["correct"].update(sample=32)
    run.run_cell(name, SEED, 1.5, False, check_device=False, shrink=many,
                 control=("int8",))
    nums = {}
    for ln in capsys.readouterr().out.splitlines():
        rec = json.loads(ln)
        if rec.get("phase") == "correct" and "name" in rec:
            nums[rec["name"]] = rec["value"]
    assert nums["control_int8_gap_mean"] > 3 * nums["served_logit_gap_mean"]
    assert nums["control_int8_gap_mean"] > 0


def test_int8_control_fails_the_training_check(capsys):
    name = next(c for c in CELLS if c.startswith("ffn"))
    rehearse(name, 0, control=("int8",))
    nums = {}
    for ln in capsys.readouterr().out.splitlines():
        rec = json.loads(ln)
        if rec.get("phase") == "correct" and "name" in rec:
            nums[rec["name"]] = (rec["value"], rec["limit"])
    value, limit = nums["control_int8_first_grad_rel_diff.w2"]
    assert value > limit > nums["first_grad_rel_diff.w2"][0]


def test_another_trainer_arrives_as_files(monkeypatch, capsys):
    """A trainer with other leaves, a bias and a scalar loss goes
    through ``benchmark/train.py`` as a driver and a reference, with no
    edit there: its loss, first gradient and parameter change are
    compared, and a step that leaves part of the state unchanged is
    refused."""
    from benchmark import harness
    from benchmark.tests import toy_trainer
    monkeypatch.setattr(harness, "driver_module", lambda config: toy_trainer)
    monkeypatch.setattr(harness, "reference_module",
                        lambda config: toy_trainer)

    def toy(cell):
        cell["config"] = {"rows": 64, "d_in": 16, "d_out": 4}
        cell["work"]["trace"].update(after_s=0.2, for_s=0.3)
        cell["work"]["correct"] = {
            "steps": 3, "grad_rel_diff": {"kernel": {"limit": 1e-4},
                                          "bias": {"limit": 1e-4}},
            "grad_norm_gap": 1e-4, "param_change_gap": 1e-4,
            "loss_gap": 1e-5}

    name = next(c for c in CELLS if c.startswith("ffn"))
    line = run.run_cell(name, SEED, 0.5, False, check_device=False,
                        shrink=toy)
    assert line["correct"] is True
    nums = {json.loads(ln).get("name") for ln in
            capsys.readouterr().out.splitlines()}
    assert {"loss_gap", "first_grad_rel_diff.kernel",
            "first_grad_rel_diff.bias"} <= nums
    frozen = run.run_cell(
        name, SEED, 0.5, False, check_device=False, shrink=toy,
        alter=lambda before, after: {**after, "bias": before["bias"]})
    assert frozen["correct"] is False


def test_refuses_to_run_off_a_tpu():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    last = (out.stdout.strip().splitlines() or [""])[-1]
    assert '"correct"' not in last
