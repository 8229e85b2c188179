"""Launcher CLIs: train.py (plain + elastic) and serve.py smoke runs."""
import os

import pytest

from repro.launch import serve as serve_cli
from repro.launch import train as train_cli


@pytest.mark.slow
def test_train_cli_elastic_cnn(capsys):
    train_cli.main([
        "--arch", "paper-cnn", "--rounds", "2", "--workers", "2",
        "--tau", "1", "--batch-size", "16"])
    out = capsys.readouterr().out
    assert "round 1" in out and "score=" in out


@pytest.mark.slow
def test_train_cli_chunked_rounds_per_call(capsys):
    """--rounds-per-call routes the CLI through round_chunk (one jit call
    for all three rounds) and still prints per-round records."""
    train_cli.main([
        "--arch", "paper-cnn", "--rounds", "3", "--workers", "2",
        "--batch-size", "8", "--rounds-per-call", "3"])
    out = capsys.readouterr().out
    assert "round 0" in out and "round 2" in out and "score=" in out


@pytest.mark.slow
def test_train_cli_plain_lm(capsys):
    train_cli.main([
        "--arch", "qwen3-4b", "--smoke", "--plain", "--rounds", "2",
        "--batch-size", "2", "--seq-len", "32"])
    out = capsys.readouterr().out
    assert "step 1" in out


@pytest.mark.slow
def test_serve_cli(capsys):
    serve_cli.main(["--arch", "stablelm-3b", "--batch", "2",
                    "--prompt-len", "8", "--steps", "4"])
    out = capsys.readouterr().out
    assert "tok/s" in out
    assert "incl. jit compile" in out  # trial 0 is labelled


@pytest.mark.slow
def test_serve_cli_eos_id_counts_real_tokens(capsys):
    """--eos-id reaches the engine's pinning path from the CLI, and the
    reported throughput excludes EOS-pinned padding (so it can only be
    ≤ batch × steps)."""
    serve_cli.main(["--arch", "qwen3-4b", "--batch", "4",
                    "--prompt-len", "8", "--steps", "12",
                    "--eos-id", "7"])
    import re

    out = capsys.readouterr().out
    toks = [int(m) for m in re.findall(r"(\d+) tokens", out)]
    assert toks and all(t <= 4 * 12 for t in toks)


@pytest.mark.slow
def test_serve_cli_continuous_traffic(capsys):
    serve_cli.main(["--arch", "qwen3-4b", "--prompt-len", "8",
                    "--steps", "6", "--capacity", "2", "--traffic", "6"])
    out = capsys.readouterr().out
    assert "req/s" in out and "p99" in out
    assert "served 6/6" in out


@pytest.mark.slow
def test_serve_cli_restore_roundtrip_multishard(tmp_path, capsys,
                                                monkeypatch):
    """An ElasticSession run saves a multi-shard elastic checkpoint; the
    serve CLI restores and serves it (no warning on the matching arch)."""
    from repro.api import ElasticSession, RunSpec
    from repro.checkpoint import checkpoint
    from repro.configs.base import ElasticConfig, OptimizerConfig

    ck = str(tmp_path / "ck")
    sess = ElasticSession(RunSpec(
        arch="stablelm-3b", smoke=True,
        optimizer=OptimizerConfig(name="sgd", lr=0.01),
        elastic=ElasticConfig(num_workers=2, tau=1, dynamic=True),
        rounds=2, seed=1, n_tokens=4000, seq_len=16, batch_size=2,
        save_path=ck))
    sess.run()
    monkeypatch.setattr(checkpoint, "MAX_SHARD_BYTES", 4096)
    sess.save()
    import os
    assert len([f for f in os.listdir(ck) if f.endswith(".npz")]) > 1

    serve_cli.main(["--arch", "stablelm-3b", "--restore", ck,
                    "--batch", "2", "--prompt-len", "8", "--steps", "4"])
    out = capsys.readouterr().out
    assert "restored" in out and "rounds=2" in out and "tok/s" in out
    assert "WARNING" not in out


@pytest.mark.slow
def test_serve_cli_restore_arch_mismatch_warns(tmp_path, capsys):
    """--restore with the wrong --arch prints the mismatch warning before
    the restore fails on the foreign parameter tree."""
    from repro.api import ElasticSession, RunSpec
    from repro.configs.base import ElasticConfig, OptimizerConfig

    ck = str(tmp_path / "ck")
    sess = ElasticSession(RunSpec(
        arch="paper-cnn", optimizer=OptimizerConfig(name="sgd", lr=0.01),
        elastic=ElasticConfig(num_workers=2, tau=1, dynamic=True),
        rounds=1, seed=0, batch_size=4, n_data=64, n_test=32,
        save_path=ck))
    sess.run()
    sess.save()
    with pytest.raises(Exception):
        serve_cli.main(["--arch", "qwen3-4b", "--restore", ck,
                        "--batch", "2", "--prompt-len", "8",
                        "--steps", "4"])
    out = capsys.readouterr().out
    assert "WARNING" in out and "paper-cnn" in out


@pytest.mark.slow
def test_train_cli_checkpoint_roundtrip(tmp_path, capsys):
    path = str(tmp_path / "ck")
    train_cli.main([
        "--arch", "paper-cnn", "--rounds", "1", "--workers", "2",
        "--batch-size", "8", "--save", path])
    from repro.checkpoint import checkpoint

    tree, meta = checkpoint.restore(path)
    assert meta["rounds"] == 1
    assert "conv1" in tree


@pytest.mark.slow
def test_train_cli_membership_plan(capsys):
    """--capacity/--membership-plan drive a live 2→1→3 resize through the
    CLI; the per-round line shows the live count against capacity."""
    train_cli.main([
        "--arch", "paper-cnn", "--rounds", "3", "--workers", "2",
        "--capacity", "4", "--batch-size", "8",
        "--membership-plan", "1:1,2:3"])
    out = capsys.readouterr().out
    assert "k=2/4" in out and "k=1/4" in out and "k=3/4" in out


@pytest.mark.slow
def test_train_cli_scale_up_defaults(capsys):
    """Regression: --membership-scenario scale_up with no explicit
    --capacity/--membership-k must default to a pool with headroom (2k)
    instead of crashing on k0 == k_to == capacity."""
    train_cli.main([
        "--arch", "paper-cnn", "--rounds", "2", "--workers", "2",
        "--batch-size", "8", "--membership-scenario", "scale_up"])
    out = capsys.readouterr().out
    assert "k=2/4" in out and "k=4/4" in out


@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["iid", "burst", "correlated",
                                      "straggler", "crash_restart"])
def test_train_cli_failure_scenarios_end_to_end(capsys, scenario):
    """Every scenario is selectable from the CLI and drives a full round
    loop (τ=2 so straggler slowdown actually bites)."""
    train_cli.main([
        "--arch", "paper-cnn", "--rounds", "2", "--workers", "2",
        "--tau", "2", "--batch-size", "8", "--failure-scenario", scenario,
        "--seed", "3"])
    out = capsys.readouterr().out
    assert "round 1" in out and "score=" in out
    if scenario == "straggler":
        assert "straggle=" in out


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []  # JAX reads the variable itself; no other dir set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
