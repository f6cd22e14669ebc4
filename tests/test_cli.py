"""CLI smoke tests — the reference's driver surface (``train_ffns.py:342-391``)
exercised end-to-end as a subprocess, plus the driver entry points."""

import json
import os
import subprocess
import sys

import pytest

from conftest import load_scaled_timeout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(*args):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # CLI sets its own via --fake_devices
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "train_ffns.py"), *args],
        capture_output=True, text=True, timeout=load_scaled_timeout(600),
        cwd=REPO, env=env)


@pytest.mark.slow
def test_cli_all_methods_verify():
    r = _run_cli("-s", "8", "-bs", "4", "-n", "16", "-l", "2", "-d", "64",
                 "-m", "0", "-r", "7", "--lr", "0.1", "--fake_devices", "8",
                 "--strict")
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "ARGS:" in out and "PARAMS:" in out
    for name in ("train_single", "train_ddp", "train_fsdp", "train_tp"):
        assert f"{name} takes" in out
    assert "SoftAssertionError" not in out


@pytest.mark.slow
@pytest.mark.serial
def test_cli_method9_verifies_every_strategy():
    """--method 9: every strategy runs and every extension is pinned to
    its oracle (hybrid==DDP(dp), PP==single, EP==dense grouped oracle,
    transformer TP==transformer single, LM TP==LM single on the real
    objective) — hard-failing under --strict."""
    r = _run_cli("-s", "8", "-bs", "8", "-n", "16", "-l", "8", "-d", "16",
                 "-m", "9", "-r", "3", "--lr", "0.1", "--fake_devices",
                 "8", "--strict", "--heads", "4", "--vocab", "64")
    assert r.returncode == 0, r.stdout + r.stderr
    for name in ("train_single", "train_ddp", "train_fsdp", "train_tp",
                 "train_hybrid", "train_pp", "train_moe_ep",
                 "train_transformer_tp", "train_moe_transformer_ep",
                 "train_lm_tp", "train_moe_lm_ep", "train_lm_seq"):
        assert f"{name} takes" in r.stdout
    assert "SoftAssertionError" not in r.stdout


@pytest.mark.slow
def test_cli_lm_gqa():
    r = _run_cli("-s", "4", "-bs", "2", "-n", "8", "-l", "2", "-d", "32",
                 "-m", "11", "-r", "3", "--fake_devices", "4", "--tp",
                 "2", "--vocab", "64", "--heads", "4", "--kv_heads", "2",
                 "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_lm_tp takes" in r.stdout
    r = _run_cli("-s", "2", "-m", "2", "--kv_heads", "2",
                 "--fake_devices", "4")
    assert r.returncode == 2 and "--kv_heads" in r.stderr
    # MQA (--kv_heads 1) with a >1 model axis: clean exit-2 arg error up
    # front, not _validate_tp's mid-run ValueError traceback
    r = _run_cli("-s", "2", "-m", "11", "--kv_heads", "1", "--heads", "4",
                 "--tp", "2", "--fake_devices", "4", "--vocab", "64")
    assert r.returncode == 2 and "model-axis" in r.stderr
    assert "Traceback" not in r.stderr


@pytest.mark.slow
def test_cli_pp_interleaved():
    r = _run_cli("-s", "2", "-bs", "8", "-n", "8", "-l", "8", "-d", "32",
                 "-m", "6", "-r", "3", "--fake_devices", "4",
                 "--pp_schedule", "interleaved", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_pp takes" in r.stdout
    # flag discipline: --pp_chunks outside interleaved exits 2; bad
    # chunking exits 2 up front (no trainer traceback)
    r = _run_cli("-s", "2", "-m", "6", "-l", "8", "--fake_devices", "4",
                 "--pp_schedule", "gpipe", "--pp_chunks", "4")
    assert r.returncode == 2 and "--pp_chunks" in r.stderr
    r = _run_cli("-s", "2", "-m", "6", "-l", "6", "--fake_devices", "4",
                 "--pp_schedule", "interleaved", "--pp_chunks", "2")
    assert r.returncode == 2 and "chunks" in r.stderr
    assert "Traceback" not in r.stderr


@pytest.mark.slow
def test_cli_attn_flag():
    r = _run_cli("-s", "2", "-bs", "2", "-n", "8", "-l", "2", "-d", "32",
                 "-m", "11", "-r", "3", "--fake_devices", "4", "--tp",
                 "2", "--vocab", "64", "--heads", "4", "--attn", "rope",
                 "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_lm_tp takes" in r.stdout
    r = _run_cli("-s", "2", "-m", "1", "--attn", "rope")
    assert r.returncode == 2 and "--attn" in r.stderr


@pytest.mark.slow
def test_cli_moe_lm_method():
    r = _run_cli("-s", "4", "-bs", "8", "-n", "8", "-l", "2", "-d", "32",
                 "-m", "12", "-r", "3", "--fake_devices", "4",
                 "--experts", "8", "--heads", "4", "--vocab", "64",
                 "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_moe_lm_ep takes" in r.stdout


@pytest.mark.slow
def test_cli_transformer_pipeline_method():
    r = _run_cli("-s", "2", "-bs", "8", "-n", "8", "-l", "4", "-d", "32",
                 "-m", "6", "-r", "3", "--fake_devices", "4",
                 "--pp_family", "transformer", "--heads", "4",
                 "--pp_schedule", "1f1b", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_transformer_pp takes" in r.stdout


@pytest.mark.slow
def test_cli_lm_pipeline_method():
    r = _run_cli("-s", "2", "-bs", "8", "-n", "8", "-l", "4", "-d", "32",
                 "-m", "6", "-r", "3", "--fake_devices", "4",
                 "--pp_family", "lm", "--heads", "4", "--vocab", "64",
                 "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_lm_pp takes" in r.stdout


def test_cli_pp_family_guard():
    r = _run_cli("-s", "2", "-m", "9", "--pp_family", "transformer",
                 "--fake_devices", "4")
    assert r.returncode == 2
    assert "--pp_family applies to --method 6" in r.stderr


@pytest.mark.slow
def test_cli_lm_method():
    r = _run_cli("-s", "4", "-bs", "4", "-n", "8", "-l", "2", "-d", "32",
                 "-m", "11", "-r", "3", "--fake_devices", "4", "--tp", "4",
                 "--heads", "4", "--vocab", "64", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_lm_tp takes" in r.stdout


@pytest.mark.slow
def test_cli_hybrid_method():
    r = _run_cli("-s", "4", "-bs", "2", "-n", "16", "-l", "2", "-d", "64",
                 "-m", "5", "-r", "3", "--fake_devices", "8", "--tp", "2")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_hybrid takes" in r.stdout


@pytest.mark.slow
def test_cli_moe_ep_method():
    r = _run_cli("-s", "4", "-bs", "4", "-n", "16", "-l", "2", "-d", "32",
                 "-m", "7", "-r", "3", "--fake_devices", "4", "--experts",
                 "8", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_moe_ep takes" in r.stdout


@pytest.mark.slow
def test_cli_transformer_method():
    r = _run_cli("-s", "2", "-bs", "2", "-n", "16", "-l", "2", "-d", "32",
                 "-m", "8", "-r", "3", "--fake_devices", "4", "--heads",
                 "4", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_transformer_tp takes" in r.stdout


@pytest.mark.slow
def test_cli_checkpoint_resume(tmp_path):
    """A CLI run with --checkpoint_dir publishes restorable checkpoints whose
    final params equal an in-process run on the same schedule; a second
    invocation resumes (trains 0 remaining steps) without error."""
    import numpy as np
    from distributed_llm_code_samples_tpu.checkpoint import (
        latest_step, restore_checkpoint)
    from distributed_llm_code_samples_tpu.data import make_seed_schedule
    from distributed_llm_code_samples_tpu.models import init_ffn_stack
    from distributed_llm_code_samples_tpu.parallel import train_single

    ck = str(tmp_path / "ck")
    args = ("-s", "4", "-bs", "2", "-n", "16", "-l", "2", "-d", "64",
            "-m", "1", "-r", "7", "--lr", "0.1", "--fake_devices", "1",
            "--checkpoint_dir", ck, "--checkpoint_every", "2")
    r = _run_cli(*args)
    assert r.returncode == 0, r.stdout + r.stderr
    method_dir = os.path.join(ck, "train_single")
    assert latest_step(method_dir) == 4

    import jax
    params = init_ffn_stack(jax.random.PRNGKey(7), 64, 2)
    seeds = make_seed_schedule(4, random_seed=7)
    oracle = train_single(params, seeds, 2 * 16, 64, lr=0.1)
    got, step, _ = restore_checkpoint(method_dir, params)
    assert step == 4
    np.testing.assert_allclose(np.asarray(got.w1), np.asarray(oracle.w1),
                               rtol=1e-6, atol=1e-7)

    r2 = _run_cli(*args)  # resume: nothing left to train
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert latest_step(method_dir) == 4


def test_graft_entry_fn_is_jittable():
    import jax
    import __graft_entry__ as g  # conftest puts the repo root on sys.path
    fn, args = g.entry()
    y = jax.jit(fn)(*args)
    jax.block_until_ready(y)
    assert y.shape == (512, 256)


@pytest.mark.slow
def test_graft_dryrun_multichip():
    # the full multi-chip surface in one test (~2-3 min on CPU): worth
    # running, but not inside the tier-1 wall-clock budget
    import __graft_entry__ as g
    g.dryrun_multichip(8)  # conftest provides 8 fake CPU devices


@pytest.mark.slow
def test_cli_moe_transformer_method():
    r = _run_cli("-s", "4", "-bs", "4", "-n", "8", "-l", "2", "-d", "32",
                 "-m", "10", "-r", "3", "--fake_devices", "4", "--experts",
                 "8", "--heads", "4", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train_moe_transformer_ep takes" in r.stdout


@pytest.mark.slow
def test_cli_comm_pallas_ring():
    """--method 2 --comm pallas_ring: DDP's gradient reduction through
    the hand-scheduled RDMA ring kernel, end to end from the flag
    surface."""
    r = _run_cli("-m", "2", "-s", "8", "-bs", "4", "-n", "8", "-l", "2",
                 "-d", "32", "--comm", "pallas_ring",
                 "--fake_devices", "8")
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.slow
def test_cli_method13_seq_parallel_lm():
    """--method 13: the long-context LM over the seq axis from the flag
    surface — ring (default), ulysses, and the flash-fused ring."""
    for extra in ((), ("--seq_impl", "ulysses"), ("--attn", "flash")):
        r = _run_cli("-m", "13", "-s", "4", "-bs", "2", "-n", "32", "-l",
                     "2", "-d", "32", "--heads", "4",
                     "--fake_devices", "8", *extra)
        assert r.returncode == 0, (extra, r.stdout + r.stderr)
    # guards: rope unsupported, GQA unsupported
    r = _run_cli("-m", "13", "-s", "2", "-n", "32", "--attn", "rope",
                 "--fake_devices", "8")
    assert r.returncode == 2 and "not supported by --method 13" in r.stderr
    r = _run_cli("-m", "13", "-s", "2", "-n", "32", "--heads", "4",
                 "--kv_heads", "2", "--fake_devices", "8")
    assert r.returncode == 2 and "full MHA only" in r.stderr


@pytest.mark.slow
def test_cli_comm_pallas_ring_fsdp():
    """--method 3 --comm pallas_ring: FSDP's gathers AND reduce-scatters
    through the hand-scheduled ring kernels from the flag surface."""
    r = _run_cli("-m", "3", "-s", "8", "-bs", "4", "-n", "8", "-l", "2",
                 "-d", "64", "--comm", "pallas_ring",
                 "--fake_devices", "8")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_comm_flag_guards():
    """--comm pallas_ring outside methods 2/3 (or with --zero1) is a
    clean exit-2 arg error, never a silent psum fallback."""
    r = _run_cli("-s", "2", "-m", "2", "--zero1", "--comm", "pallas_ring",
                 "--fake_devices", "4")
    assert r.returncode == 2 and "--zero1" in r.stderr
    r = _run_cli("-s", "2", "-m", "4", "--comm", "pallas_ring",
                 "--fake_devices", "4")
    assert r.returncode == 2 and "--comm applies" in r.stderr


def test_cli_head_flag():
    """--head fused swaps the LM head for the fused Pallas kernels on
    method 11 (vocab-parallel merge) and method 13 (per-shard blocks);
    both run end to end on the fake mesh."""
    r = _run_cli("-s", "2", "-bs", "2", "-n", "8", "-l", "2", "-d", "32",
                 "-m", "11", "-r", "3", "--fake_devices", "4", "--tp",
                 "2", "--vocab", "64", "--heads", "4", "--head", "fused",
                 "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_cli("-s", "2", "-bs", "2", "-n", "16", "-l", "2", "-d", "32",
                 "-m", "13", "-r", "3", "--fake_devices", "4", "--vocab",
                 "64", "--heads", "4", "--head", "fused", "--attn",
                 "flash", "--lr", "0.1")
    assert r.returncode == 0, r.stdout + r.stderr


# --- the differential check's verdict (cli.strategy_disagreement) ---------
#
# Planted faults, each on the same synthetic "trained" weight: what a
# strategy bug does must fail, what a ReLU sign flip does must not.

def _trained(rows=400, cols=64, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    init = (0.02 * rng.standard_normal((1, rows, cols))).astype(np.float32)
    a = init + (1e-3 * rng.standard_normal(init.shape)).astype(np.float32)
    return rng, init, a


def _flip(rng, a, rows, size):
    """One token's term ``size * x`` added to each of ``rows``."""
    b = a.copy()
    for r in rows:
        b[0, r] += (size * rng.standard_normal(a.shape[-1])).astype(a.dtype)
    return b


def _row_not_updated(a, init, row):
    b = a.copy()
    b[0, row] = init[0, row]
    return b


_PLANTED = {
    # name: (fault, failed?)
    "identical": (lambda rng, init, a: a.copy(), False),
    "inside_tolerance": (lambda rng, init, a: a * (1 + 5e-6), False),
    "relu_flips_in_three_rows":
        (lambda rng, init, a: _flip(rng, a, (3, 77, 310), 1e-5), False),
    "flips_in_too_many_rows":
        (lambda rng, init, a: _flip(rng, a, range(0, 400, 20), 1e-5), True),
    "a_row_off_by_its_update":
        (lambda rng, init, a: _flip(rng, a, (5,), 1e-3), True),
    "a_row_never_updated":
        (lambda rng, init, a: _row_not_updated(a, init, 100), True),
    "a_quarter_of_the_reduction_lost":
        (lambda rng, init, a: init + 0.75 * (a - init), True),
    "dense_error_ten_times_the_tolerance":
        (lambda rng, init, a: a + 3e-6, True),
}


@pytest.mark.parametrize("name", sorted(_PLANTED))
def test_strategy_disagreement_planted(name):
    from distributed_llm_code_samples_tpu.cli import strategy_disagreement
    fault, want_failed = _PLANTED[name]
    rng, init, a = _trained()
    failed, text = strategy_disagreement(a, fault(rng, init, a), init,
                                         1e-5, 1e-7)
    assert failed is want_failed, (name, text)
    # silent only where every element is inside the tolerance
    assert (text is None) == (name in ("identical", "inside_tolerance"))


def test_strategy_disagreement_vectors_get_no_exception():
    """Rows are a matrix's; a 1-D array (a bias, a gain) is held to the
    elementwise tolerance alone."""
    import numpy as np
    from distributed_llm_code_samples_tpu.cli import strategy_disagreement
    init = np.zeros(512, np.float32)
    a = init + 1e-3
    b = a.copy()
    b[7] += 1e-5
    assert strategy_disagreement(a, b, init, 1e-5, 1e-7)[0] is True


def test_cli_strict_fails_on_a_planted_strategy_fault(monkeypatch, capsys):
    """Through the CLI: FSDP handed back with a quarter of its update
    lost -> ``-m 0 --strict`` returns 1 and names the pair."""
    import jax
    from distributed_llm_code_samples_tpu import cli, parallel
    name, fsdp = parallel.STRATEGIES[3]
    keep = {}

    def faulty(params, *args, **kwargs):
        keep["init"] = jax.tree_util.tree_map(lambda w: w + 0, params)
        out = fsdp(params, *args, **kwargs)
        return jax.tree_util.tree_map(lambda w, w0: w0 + 0.75 * (w - w0),
                                      out, keep["init"])

    monkeypatch.setitem(parallel.STRATEGIES, 3, (name, faulty))
    argv = ["-s", "8", "-bs", "4", "-n", "16", "-l", "2", "-d", "32",
            "-m", "0", "-r", "7", "--lr", "0.1"]    # 8 devices: conftest
    assert cli.main(argv) == 0              # soft by default ...
    out = capsys.readouterr().out
    assert "SoftAssertionError: ddp.w1 vs fsdp.w1" in out
    assert "compared ddp vs fsdp: DISAGREE" in out
    assert "compared 1dev vs tp: agree" in out
    assert cli.main([*argv, "--strict"]) == 1   # ... hard under --strict
