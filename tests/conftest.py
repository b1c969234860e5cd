"""Shared tiny-model fixtures.

Tests run on 8 host CPU devices, so the mesh tests (the CPU rehearsal of
the multi-chip paths) always run.  The device-count flag is appended to any
``XLA_FLAGS`` the caller set, before JAX starts a backend; a count the
caller chose is kept."""
from __future__ import annotations

import os

_FLAGS = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.config import DecodeConfig, ModelConfig  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long property-based tests (CI runs -m 'not slow' "
        "per push; the full suite runs nightly)")
    config.addinivalue_line(
        "markers", "serving: continuous-batching serving engine tests")
    config.addinivalue_line(
        "markers", "sharded: host-mesh sharded decode tests (on the 8 "
        "host devices this conftest sets up)")


def tiny_dense(**kw) -> ModelConfig:
    base = dict(name="tiny-dense", num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=2, d_ff=128, vocab_size=97, bpd_k=4,
                max_seq_len=512, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def tiny_moe(**kw) -> ModelConfig:
    base = dict(name="tiny-moe", family="moe", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=97,
                mlp_type="moe", num_experts=4, num_experts_per_tok=2,
                num_shared_experts=1, shared_expert_d_ff=64, bpd_k=4,
                max_seq_len=512, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def tiny_rwkv(**kw) -> ModelConfig:
    base = dict(name="tiny-rwkv", family="ssm", num_layers=2, d_model=64,
                block_type="rwkv6", mlp_type="rwkv_channel_mix",
                rwkv_head_dim=32, d_ff=128, vocab_size=97, bpd_k=4,
                max_seq_len=512, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def tiny_hymba(**kw) -> ModelConfig:
    base = dict(name="tiny-hymba", family="hybrid", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, block_type="hymba", d_ff=128,
                vocab_size=97, bpd_k=4, ssm_state_dim=8, num_meta_tokens=4,
                sliding_window=32, global_attn_layers=(0,),
                max_seq_len=512, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def tiny_seq2seq(**kw) -> ModelConfig:
    base = dict(name="tiny-s2s", family="seq2seq", is_encoder_decoder=True,
                num_encoder_layers=2, num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=4, d_ff=128, vocab_size=97, bpd_k=4,
                max_seq_len=512, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


FAMILY_CONFIGS = {
    "dense": tiny_dense,
    "moe": tiny_moe,
    "rwkv6": tiny_rwkv,
    "hymba": tiny_hymba,
}


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def random_tokens(key, cfg: ModelConfig, b: int, s: int):
    return jax.random.randint(key, (b, s), 0, cfg.vocab_size)
