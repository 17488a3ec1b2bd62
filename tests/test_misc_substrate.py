"""Checkpointing, token streams, codec cost models, bandwidth ledger."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro.core.bandwidth import BandwidthLedger
from repro.data import codec
from repro.data.tokens import StreamConfig, TokenStream


def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.bfloat16), "d": [jnp.zeros(2), jnp.ones(1)]}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        checkpoint.save(path, tree)
        got = checkpoint.load(path, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_token_stream_drifts():
    s = TokenStream(StreamConfig(vocab_size=64, seed=3, drift_period=100.0))
    r = np.random.default_rng(0)
    a = s.sample(r, batch=4, seq=128, t=0.0)
    assert a.shape == (4, 129)
    assert a.min() >= 0 and a.max() < 64
    # distribution drifts: unigram histograms at opposite drift phases
    # (sin peaks: t = T/4 vs 3T/4) differ
    r1, r2 = np.random.default_rng(1), np.random.default_rng(1)
    h0 = np.bincount(s.sample(r1, 8, 256, 25.0).ravel(), minlength=64)
    h1 = np.bincount(s.sample(r2, 8, 256, 75.0).ravel(), minlength=64)
    h0 = h0 / h0.sum()
    h1 = h1 / h1.sum()
    assert np.abs(h0 - h1).sum() > 0.1


def test_codec_monotonic():
    px = 64 * 64
    assert codec.jpeg_bytes(px) > 0
    one = codec.h264_buffer_bytes(1, px, 10.0)
    many = codec.h264_buffer_bytes(10, px, 10.0)
    assert one <= many or many == codec.h264_buffer_bytes(10, px, 10.0)
    # buffered H.264 beats per-frame JPEG at the same frame count
    assert codec.h264_buffer_bytes(10, px, 10.0) < 10 * codec.jpeg_bytes(px)


def test_bandwidth_ledger():
    led = BandwidthLedger()
    led.uplink(1000, 0.0)
    led.downlink(4000, 1.0)
    up, down = led.kbps(8.0)
    assert up == pytest.approx(1.0)
    assert down == pytest.approx(4.0)


def test_compile_cache_dir_from_env_or_fixed_path(monkeypatch, tmp_path):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, else to the checkout's fixed `.jax_cache` — never a temporary
    or per-process name."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.configure_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.configure_compile_cache() == path  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
