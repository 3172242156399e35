"""``chip_smoke.py`` and what PR 21 took out so a broken chip path cannot pass.

The script's real run is on the chip (through the chip tool); here its
phases run at tiny size on the CPU with the kernels in interpret mode, and
its no-accelerator exit, the one compile-cache rule and the
flash-attention dispatch policy are pinned.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention as attention_ops
from deepspeed_tpu.ops.attention import attention, reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GPT = dict(vocab_size=2048, hidden_size=64, n_layers=2, n_heads=2,
                max_seq_len=64, activation="gelu", use_rmsnorm=False,
                use_rope=False, tie_embeddings=True, attn_impl="pallas")
TINY_LLAMA = dict(vocab_size=256, hidden_size=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden_size=128, max_seq_len=64)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def counter(smoke):
    return smoke.CompileCounter()


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


# -- no accelerator: no result ---------------------------------------------
def test_smoke_without_accelerator_fails_in_one_line():
    out = _run(os.path.join(REPO, "chip_smoke.py"), REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and '"phase"' not in out.stdout
    reason = [ln for ln in out.stderr.splitlines() if "chip_smoke:" in ln]
    assert len(reason) == 1 and "no accelerator" in reason[0]
    assert "Traceback" not in out.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run("chip_smoke.py", tmp_path)
    assert out.returncode != 0 and '"ok"' not in out.stdout


# -- one compile-cache rule -------------------------------------------------
def test_compile_cache_leaves_the_environment_alone(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a: pytest.fail(f"jax.config.update{a} with the variable set"))
    assert compile_cache.enable_compile_cache() == "/some/dir"


def test_compile_cache_default_is_fixed_and_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    assert compile_cache.enable_compile_cache() == compile_cache.DEFAULT_DIR
    assert updates == {"jax_compilation_cache_dir": compile_cache.DEFAULT_DIR}
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- flash attention dispatch: raise or say so, never silently --------------
def _qkv(batch=2, seq=128, heads=4, dim=16, dtype=jnp.float32):
    key = jax.random.key(0)
    return [jax.random.normal(jax.random.fold_in(key, i),
                              (batch, seq, heads, dim), dtype)
            for i in range(3)]


def test_pallas_on_a_shape_that_does_not_tile_raises():
    q, k, v = _qkv(seq=600)         # past one tile, and 600 % 128 != 0
    with pytest.raises(ValueError, match="cannot tile"):
        flash_attention(q, k, v, interpret=True)
    with pytest.raises(ValueError, match="does not tile"):
        attention(q, k, v, impl="pallas", interpret=True)


def test_auto_on_a_shape_that_does_not_tile_says_so(monkeypatch):
    warned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention_ops, "_warn_fallback", warned.append)
    q, k, v = _qkv(seq=384, heads=2)            # 384 % 256 != 0
    out = attention(q, k, v, impl="auto", block_q=256, block_k=256)
    np.testing.assert_allclose(out, reference_attention(q, k, v), atol=1e-6)
    assert len(warned) == 1 and "does not tile" in warned[0]


def test_flash_runs_per_shard_on_a_mesh(mesh_1d):
    """XLA cannot partition a Mosaic kernel; under ``with mesh:`` the
    kernel goes through shard_map, batch over the data axes."""
    q, k, v = _qkv(batch=8)
    with mesh_1d:
        jaxpr = str(jax.make_jaxpr(lambda *a: attention(
            *a, impl="pallas", interpret=True))(q, k, v))
        out = attention(q, k, v, impl="pallas", interpret=True)
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    np.testing.assert_allclose(out, reference_attention(q, k, v), atol=2e-5)


# -- the phases, tiny, kernels interpreted ----------------------------------
def test_phase_kernels(smoke):
    errors = smoke.phase_kernels(
        flash_shape=(1, 64, 2, 16), heads=(4, 2), head_dim=16, page_size=8,
        decode_batch=2, max_pages=2, prefill_len=8, big_page=16,
        big_prefill_len=24, interpret=True)
    assert set(errors) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dk",
                           "flash_bwd_dv", "ragged_decode", "ragged_prefill",
                           "ragged_decode_p16", "ragged_prefill_p16"}


def test_phase_train(smoke, counter, capsys):
    losses = smoke.phase_train(counter, TINY_GPT, seq=64, micro_batch=1,
                               gas=2, steps=2, on_chip=False)
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert '"flash_kernel_in_step": true' in capsys.readouterr().out


def test_phase_train_refuses_a_step_without_the_kernel(smoke, counter):
    with pytest.raises(smoke.SmokeError, match="no Pallas kernel"):
        smoke.phase_train(counter, dict(TINY_GPT, attn_impl="reference"),
                          seq=64, micro_batch=1, gas=2, steps=1,
                          on_chip=False)


def test_phase_serve(smoke, counter, capsys):
    smoke.phase_serve(counter, TINY_LLAMA, max_batch=2, page_size=8,
                      max_seq=32, n_requests=3, prompt_range=(4, 16),
                      new_tokens=4, n_reference=1,
                      attention_backend="pallas-interpret", on_chip=False)
    out = capsys.readouterr().out
    assert '"requests_finished": 3' in out and '"leaks": {}' in out


def test_phase_serve_refuses_the_jnp_backend(smoke, counter):
    """On the CPU ``"auto"`` resolves to the gather path — the engine says
    so (``attention_impl``) and the smoke refuses it."""
    with pytest.raises(smoke.SmokeError, match="resolved to 'jnp'"):
        smoke.phase_serve(counter, TINY_LLAMA, max_batch=2, page_size=8,
                          max_seq=32, n_requests=2, prompt_range=(4, 8),
                          new_tokens=2, n_reference=1,
                          attention_backend="auto", on_chip=False)


def test_phase_sharded(smoke, counter, capsys):
    """fsdp over the 8 virtual devices against one of them."""
    smoke.phase_sharded(counter, TINY_GPT, seq=64, micro_batch=1, steps=2,
                        large_leaf=100_000, on_chip=False)
    out = capsys.readouterr().out
    assert '"mesh": {"fsdp": 8}' in out
    assert '"replicated_large_leaves": 0' in out
