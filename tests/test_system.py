"""End-to-end behaviour tests: training loop, restart, serving."""
import os
import sys

import jax
import numpy as np
import pytest

from repro.launch.serve import run as serve_run
from repro.launch.train import run as train_run


def test_train_loss_decreases(tmp_path):
    out = train_run("qwen3-4b", smoke=True, steps=15, seq_len=64,
                    global_batch=4, ckpt_dir=str(tmp_path), ckpt_every=50,
                    lr=1e-3, log_every=100)
    losses = out["losses"]
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


def test_train_restart_resumes(tmp_path):
    train_run("mamba2-370m", smoke=True, steps=6, seq_len=32,
              global_batch=4, ckpt_dir=str(tmp_path), ckpt_every=6,
              log_every=100)
    out = train_run("mamba2-370m", smoke=True, steps=3, seq_len=32,
                    global_batch=4, ckpt_dir=str(tmp_path), ckpt_every=50,
                    log_every=100)
    # restart restored from step 6 and kept training without divergence
    assert len(out["losses"]) == 3
    assert all(np.isfinite(out["losses"]))


def test_serving_continuous_batching():
    results = serve_run("qwen3-4b", smoke=True, n_requests=5, slots=2,
                        prompt_len=8, max_new=6, max_len=32)
    assert len(results) == 5
    assert all(len(v) == 6 for v in results.values())


def test_serving_moe_arch():
    results = serve_run("olmoe-1b-7b", smoke=True, n_requests=3, slots=3,
                        prompt_len=6, max_new=4, max_len=24)
    assert len(results) == 3


def test_train_host_mesh_cuts_depth():
    """``--mesh host`` spans every local device on the model axis, and
    ``n_layers`` cuts the depth with the widths unchanged."""
    out = train_run("qwen3-4b", smoke=True, steps=1, seq_len=16,
                    global_batch=2, mesh_kind="host", n_layers=1,
                    log_every=100)
    wq = out["params"]["layers"]["wq"]
    assert wq.shape[0] == 1 and wq.shape[1] == 64
    assert len(wq.sharding.mesh.devices.ravel()) == len(jax.local_devices())
    assert np.isfinite(out["losses"][0]) and np.isfinite(
        out["grad_norms"][0])


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed path
    inside the checkout."""
    from repro.launch import cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            want = os.path.join(root, ".cache", "jax")
            assert cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_cli_full_flag_reaches_published_widths(monkeypatch):
    """``--full`` serves get_bundle(arch, smoke=False); the default stays
    the smoke bundle."""
    import repro.launch.serve as serve
    seen = []
    monkeypatch.setattr(serve, "run",
                        lambda arch, **kw: seen.append(kw["smoke"]) or {})
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    for argv in (["--arch", "qwen3-4b"], ["--arch", "qwen3-4b", "--full"]):
        monkeypatch.setattr(sys, "argv", ["serve", *argv])
        serve.main()
    assert seen == [True, False]
