"""``chip_smoke.py`` rehearsed on the CPU (the first two rehearsals of
the on-chip-measurement guide, kept as tests): the same script, the
same phases and checks, at toy sizes, each in a child process as the
chip tool would start it.

The sizes are shrunk HERE, in the child's prologue — the program has no
option for it — and the one assertion the rehearsal must stand in for is
``require_tpu``. Everything else runs as it does on the chip, so a
wrong argv, a phase that stops checking, or a last line that drifts
from the fixed object fails here at no chip time.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import load_scaled_timeout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child's prologue: toy sizes, the platform assertion stood in for,
# a peak for the CPU so the MFU check has something to divide by (small
# enough that a step record's ``round(mfu, 4)`` cannot read 0.0: at 1e12
# the second shape's 9.4 MFLOP step read 0.0 whenever its first chunk,
# compile included, took over 0.19 s), and allocator statistics the CPU
# does not keep
TOY = r"""
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs
from distributed_llm_code_samples_tpu.runtime import telemetry

cs.SERVE.update(model=["-d", "64", "-l", "2", "--heads", "4",
                       "--max_seq_len", "64"],
                vocab=250, vocab_tp=256, prompt_lens="5,9,13,17",
                max_new=6)
cs.TRAIN[:] = [dict(argv=["-d", "64", "-l", "2", "-n", "16", "-bs", "4",
                          "-s", "4", "--lr", "0.1"], moves=True),
               dict(argv=["-d", "32", "-l", "3", "-n", "16", "-bs", "4",
                          "-s", "4", "--lr", "0.1"], moves=False)]
cs.TRAIN_LM[:] = ["-d", "32", "-l", "2", "--heads", "4", "--vocab", "64",
                  "-n", "16", "-bs", "4", "-s", "4", "--lr", "0.1"]


cs.require_tpu = cs.describe_devices
cs.peak_bytes = lambda device: 1      # the CPU keeps no allocator statistics
peak = telemetry.peak_flops
telemetry.peak_flops = lambda kind: peak(kind) or 1e9
"""


def _child(body: str, devices: int, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, "-c", TOY.format(repo=REPO) + body],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=load_scaled_timeout(600))


def _records(stdout: str) -> list[dict]:
    return [json.loads(line) for line in stdout.strip().splitlines()]


def _assert_result_line(rec: dict, count: int) -> None:
    """The fixed object, and nothing else in it."""
    assert rec == {"ok": True,
                   "device": {"platform": "cpu", "kind": rec["device"]["kind"],
                              "count": count}}
    assert isinstance(rec["device"]["kind"], str)


def test_default_phases_at_toy_size(tmp_path):
    """serve (twice, same tokens, nothing compiled the second time,
    report rc 0) -> the toy hybrid, the toy latent-attention expert
    model, then the toy gated-convolution expert model, each against its
    plain reference -> train
    at both shapes; every stdout line is one JSON object and the last
    is the fixed one."""
    r = _child("sys.exit(cs.main([]))", 1, tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    recs = _records(r.stdout)
    assert [x["phase"] for x in recs[:-1]] == [
        "serve_gather", "serve_gather_again", "serve_report",
        "serve_hybrid", "serve_latent_moe", "serve_conv_moe",
        "train_single_0", "train_single_1", "total"]
    _assert_result_line(recs[-1], 1)
    again = recs[1]
    assert again["cache_misses"] == 0 and again["tokens"] == 4 * 6
    for served in recs[3:6]:    # the hybrid, latent + experts, conv + experts
        assert served["tokens"] == 5 * 24
        assert served["tokens_compared"] > 100
    assert all(m > 0 for x in recs[6:8] for m in x["mfu"])
    assert "devices: platform=cpu" in r.stderr     # each entry says where


def test_chips4_runs_only_the_cross_chip_phases(tmp_path):
    """``--chips 4`` on four virtual devices: all four strategies with
    the CLI's differential check, --tp 4 against --tp 1, the LM trainer
    over four devices — and none of the one-chip phases. count is 4."""
    r = _child("sys.exit(cs.main(['--chips', '4']))", 4, tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    recs = _records(r.stdout)
    assert [x["phase"] for x in recs[:-1]] == [
        "train_all_strategies", "serve_tp1", "serve_tp4", "train_lm_tp4",
        "total"]
    _assert_result_line(recs[-1], 4)
    assert recs[0]["programs_over_4_partitions"] >= 3
    assert recs[0]["argv"][-1] == "--strict"
    assert [ln.split(":")[0] for ln in recs[0]["cli_check"]] == [
        "compared ddp vs fsdp", "compared 1dev vs tp"]
    loss = recs[3]["loss"]
    assert len(loss) == 4 and loss == sorted(loss, reverse=True)


def test_chips4_fails_where_the_cli_check_did_not_run(tmp_path):
    """Phase (a) is ``-m 0 --strict``'s return code — and a 0 from a
    run that compared nothing must not pass: sent through ``-m 2`` (DDP
    alone, return code 0, no comparison), the phase fails."""
    r = _child("""
main = cs.cli.main
def only_ddp(argv):
    argv = list(argv)
    argv[argv.index('-m') + 1] = '2'
    return main(argv)
cs.cli.main = only_ddp
cs.phase_cross_chip('unused')
""", 4, tmp_path)
    assert r.returncode != 0
    assert "did not report two agreeing pairs" in r.stderr


def test_chips4_fails_without_allocator_statistics(tmp_path):
    """A device that reports no peak bytes fails like one that reports
    zero: "every device held buffers" is not shown by a missing
    number."""
    r = _child("cs.peak_bytes = lambda device: None\n"
               "cs.phase_cross_chip('unused')", 4, tmp_path)
    assert r.returncode != 0
    assert "an idle device" in r.stderr


def test_chips4_refuses_another_device_count(tmp_path):
    """Eight devices where four were asked for is a failure, not a
    wider mesh."""
    r = _child("sys.exit(cs.main(['--chips', '4']))", 8, tmp_path)
    assert r.returncode != 0
    assert "needs 4 devices" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_to_pass_off_the_chip(tmp_path, argv):
    """As the driver's sandbox runs it: no accelerator, so a non-zero
    exit and no result line — whatever the phases would have done."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        *argv], capture_output=True, text=True, env=env,
                       cwd=str(tmp_path), timeout=load_scaled_timeout(300))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr
