"""Serving-path extras: int8 KV-cache correctness, ring-buffer windows,
decode-unroll equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import build_model
from repro.models.transformer import RunOpts


@pytest.fixture(scope="module")
def dense():
    cfg = get_arch("qwen3-4b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prefill_decode(model, params, tokens, opts, S):
    _, cache = model.prefill(params, {"tokens": tokens[:, :S]}, S + 4, opts)
    logits = []
    for i in range(3):
        lg, cache = model.decode_step(
            params, cache, tokens[:, S + i : S + i + 1], jnp.int32(S + i), opts
        )
        logits.append(np.asarray(lg[:, 0], np.float32))
    return logits


def test_int8_cache_matches_bf16_topk(dense):
    cfg, model, params = dense
    S = 16
    tokens = jax.random.randint(jax.random.key(5), (2, S + 4), 0, cfg.vocab_size, jnp.int32)
    ref = _prefill_decode(model, params, tokens, RunOpts(), S)
    q = _prefill_decode(model, params, tokens, RunOpts(int8_kv_cache=True), S)
    for a, b in zip(ref, q):
        # int8 quantization noise must not change the decisions materially
        assert np.argmax(a) == np.argmax(b) or np.corrcoef(a.ravel(), b.ravel())[0, 1] > 0.98


def test_decode_unroll_matches_scan(dense):
    cfg, model, params = dense
    S = 12
    tokens = jax.random.randint(jax.random.key(6), (1, S + 4), 0, cfg.vocab_size, jnp.int32)
    a = _prefill_decode(model, params, tokens, RunOpts(decode_unroll=False), S)
    b = _prefill_decode(model, params, tokens, RunOpts(decode_unroll=True), S)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=2e-2, rtol=2e-2)


def test_sliding_window_single_layer_evicts():
    """Single attention layer: a KV slot whose position left the window must
    not influence the decode output (ring-buffer masking)."""
    import dataclasses

    from repro.models import layers
    from repro.models.common import init_params

    cfg = dataclasses.replace(
        get_arch("mixtral-8x7b").reduced(), num_layers=1, window=4
    )
    params = init_params(layers.attention_spec(cfg), jax.random.key(0))
    B, T, KVH, hd = 1, 8, cfg.num_kv_heads, cfg.resolved_head_dim

    key = jax.random.key(1)
    cache = {
        "k": jax.random.normal(key, (B, T, KVH, hd), jnp.bfloat16),
        "v": jax.random.normal(jax.random.fold_in(key, 1), (B, T, KVH, hd), jnp.bfloat16),
        "pos_ids": jnp.arange(T, dtype=jnp.int32),  # positions 0..7 resident
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, cfg.d_model), jnp.bfloat16)
    pos = jnp.int32(8)  # new token at position 8: window covers 5..8 only

    y1, _ = layers.decode_attention(params, cache, x, pos, cfg)
    # clobber slots holding positions 1 and 2 (evicted: 8 - pos >= window 4)
    cache2 = dict(cache)
    cache2["k"] = cache["k"].at[:, 1:3].set(99.0)
    cache2["v"] = cache["v"].at[:, 1:3].set(-99.0)
    y2, _ = layers.decode_attention(params, cache2, x, pos, cfg)
    np.testing.assert_allclose(
        np.asarray(y1, np.float32), np.asarray(y2, np.float32), atol=1e-6
    )
    # ...while a slot INSIDE the window does change the output
    cache3 = dict(cache)
    cache3["v"] = cache["v"].at[:, 6].set(-99.0)
    y3, _ = layers.decode_attention(params, cache3, x, pos, cfg)
    assert np.abs(np.asarray(y1, np.float32) - np.asarray(y3, np.float32)).max() > 1e-3


def test_served_weights_are_stored_in_the_compute_dtype():
    """A server stores bf16 weights (no float32 master copy); the outputs
    are the same as float32 weights cast at use, and the training
    footprint is still counted in float32."""
    from repro.dist import param_shardings, train_state_bytes
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import init_params, serving_config
    from repro.config import ShardingLayout
    from repro.models.common import param_bytes

    full = serving_config("qwen3-4b", reduced=False)
    assert full.param_dtype == "bfloat16" and full.d_model == 2560
    assert full.num_layers == 36

    cfg = serving_config("qwen3-4b")
    served, trained = build_model(cfg), build_model(get_arch("qwen3-4b").reduced())
    assert param_bytes(served.specs) * 2 == param_bytes(trained.specs)
    assert train_state_bytes(served) == train_state_bytes(trained)

    mesh = make_host_mesh()
    p16 = init_params(served, param_shardings(served.specs, mesh, ShardingLayout()))
    p32 = init_params(trained, param_shardings(trained.specs, mesh, ShardingLayout()))
    assert {x.dtype for x in jax.tree_util.tree_leaves(p16)} == {jnp.dtype(jnp.bfloat16)}
    tokens = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab_size, jnp.int32)
    a = served.forward(p16, {"tokens": tokens})[0]
    b = trained.forward(p32, {"tokens": tokens})[0]
    np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_compile_cache_dir_from_env_or_repo_root(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/elsewhere")
        assert compile_cache.use_compile_cache() == "/placed/elsewhere"
        # JAX reads the variable itself; nothing here overrides it
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent == compile_cache.pathlib.Path(__file__).parents[1]
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
