"""Launcher-layer units: input specs, shape-grid adaptation, collective-HLO
parsing, buffer padding — everything the dry-run relies on that can be
checked without 512 devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import INPUT_SHAPES, DecodeConfig, get_config
from repro.launch import steps as steps_lib
from repro.launch.hlo import collective_bytes
from repro.models.cache import attn_buf_len


def test_input_specs_cover_grid():
    for arch in ("granite-3-8b", "llava-next-34b", "hubert-xlarge"):
        cfg = get_config(arch, smoke=True)
        for shape in INPUT_SHAPES:
            spec = steps_lib.input_specs(cfg, shape)
            b = INPUT_SHAPES[shape]["global_batch"]
            for name, s in spec.items():
                assert s.shape[0] == b, (arch, shape, name)


def test_input_specs_audio_train_has_mask_and_targets():
    cfg = get_config("hubert-xlarge", smoke=True)
    spec = steps_lib.input_specs(cfg, "train_4k")
    assert set(spec) == {"frame_embeds", "mask", "targets"}
    spec = steps_lib.input_specs(cfg, "prefill_32k")
    assert set(spec) == {"frame_embeds"}


def test_vlm_text_len_subtracts_patches():
    cfg = get_config("llava-next-34b", smoke=True)
    spec = steps_lib.input_specs(cfg, "train_4k")
    s = INPUT_SHAPES["train_4k"]["seq_len"]
    assert spec["tokens"].shape[1] == s - cfg.num_patch_tokens
    assert spec["patch_embeds"].shape[1] == cfg.num_patch_tokens


def test_adapt_config_skips_encoder_only_decode():
    cfg = get_config("hubert-xlarge")
    assert steps_lib.adapt_config(cfg, "decode_32k") is None
    assert steps_lib.adapt_config(cfg, "long_500k") is None
    assert steps_lib.adapt_config(cfg, "train_4k") is not None


def test_adapt_config_long_context_windows_dense():
    dense = get_config("granite-3-8b")
    adapted = steps_lib.adapt_config(dense, "long_500k")
    assert adapted.sliding_window == steps_lib.LONG_WINDOW
    # sub-quadratic archs run long_500k natively
    for arch in ("rwkv6-1.6b", "hymba-1.5b"):
        cfg = get_config(arch)
        assert steps_lib.adapt_config(cfg, "long_500k").sliding_window == \
            cfg.sliding_window
    # starcoder2 has a native sliding window already
    sc = get_config("starcoder2-7b")
    assert steps_lib.adapt_config(sc, "long_500k").sliding_window == \
        sc.sliding_window


def test_attn_buf_len_padded_and_window_capped():
    cfg = get_config("granite-3-8b")
    n = attn_buf_len(cfg, 0, 32768 + 64, 8)
    assert n % 256 == 0 and n >= 32768 + 64 + 8
    sw = cfg.replace(sliding_window=8192)
    n = attn_buf_len(sw, 0, 524288 + 64, 8)
    assert n % 256 == 0
    assert n <= 8192 + 8 + 255 + 1  # window-capped, not context-sized


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={{0,1}}
  %ar.1 = f32[1024]{0} all-reduce(%y), to_apply=%add
  %a2a = (f32[16,16]{1,0}, f32[16,16]{1,0}) all-to-all(%p, %q)
  %mm = f32[8,8]{1,0} dot(%a, %b)
  %ags = bf16[4,256]{1,0} all-gather-start(%z), dimensions={0}
"""
    out = collective_bytes(hlo)
    assert out["bytes_by_op"]["all-gather"] == 8 * 128 * 2 + 4 * 256 * 2
    assert out["bytes_by_op"]["all-reduce"] == 1024 * 4
    assert out["bytes_by_op"]["all-to-all"] == 2 * 16 * 16 * 4
    assert out["counts"]["all-gather"] == 2
    assert "dot" not in out["bytes_by_op"]


def test_serve_state_struct_matches_materialized():
    cfg = get_config("granite-3-8b", smoke=True).replace(dtype="float32")
    dec = DecodeConfig(max_new_tokens=8)
    struct = steps_lib.serve_state_struct(cfg, dec, batch=2, seq_len=16,
                                          max_new=8)
    state = steps_lib.materialize_serve_state(cfg, dec, batch=2, seq_len=16,
                                              max_new=8)
    s_shapes = jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), struct)
    m_shapes = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), state)
    assert jax.tree_util.tree_all(
        jax.tree_util.tree_map(lambda a, b: a == b, s_shapes, m_shapes))


def test_decode_with_chunked_prefill_matches_plain():
    """kv_chunk changes the prefill computation order, not the result."""
    from repro.core import decode as D
    from repro.models import model as M

    cfg = get_config("granite-3-8b", smoke=True).replace(dtype="float32")
    params = M.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                          cfg.vocab_size)}
    dec = DecodeConfig(max_new_tokens=8)
    t1, _ = D.bpd_decode(params, cfg, dec, batch, kv_chunk=0)
    t2, _ = D.bpd_decode(params, cfg, dec, batch, kv_chunk=8)
    np.testing.assert_array_equal(np.asarray(t1[:, :32]),
                                  np.asarray(t2[:, :32]))


def test_ring_buffer_wraparound_generation():
    """Generate past the sliding window: the ring buffer must wrap without
    corrupting decode (BPD still equals greedy)."""
    from repro.core import decode as D
    from repro.models import model as M

    cfg = get_config("starcoder2-7b", smoke=True).replace(
        dtype="float32", sliding_window=16, max_seq_len=256)
    params = M.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                          cfg.vocab_size)}
    dec = DecodeConfig(max_new_tokens=40)   # >> window of 16
    bt, _ = D.bpd_decode(params, cfg, dec, batch)
    gt, _ = D.greedy_decode(params, cfg, dec, batch)
    np.testing.assert_array_equal(np.asarray(bt[:, :48]),
                                  np.asarray(gt[:, :48]))


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_enable_compile_cache_dir(monkeypatch, env_dir):
    """The env var, when set, stands (no other dir is configured);
    otherwise the cache goes to the fixed ``<checkout>/.jax_cache``."""
    import os

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            want = os.path.join(compile_cache.CHECKOUT, ".jax_cache")
            assert got == want == jax.config.jax_compilation_cache_dir
            assert os.path.isfile(os.path.join(compile_cache.CHECKOUT,
                                               "pyproject.toml"))
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
