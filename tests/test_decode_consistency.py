"""Serving correctness: prefill+decode must reproduce the full forward pass
(per family, f32 reduced configs). MoE runs with drop-free capacity: with
finite capacity, token dropping legitimately depends on how many tokens share
a dispatch (train batch vs 1-token decode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import (ModelCtx, decode_step, forward, init_cache,
                          init_params, model_specs, prefill)

FAMS = ["qwen1.5-4b",        # dense (MHA, qkv bias)
        "granite-20b",       # dense (MQA)
        "falcon-mamba-7b",   # ssm
        "zamba2-1.2b",       # hybrid
        "qwen3-moe-30b-a3b", # moe
        "whisper-tiny",      # encdec
        "granite-4.0-h-small"]  # hybrid_moe


def _cfg(arch_id):
    cfg = reduced(get_arch(arch_id), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    return cfg


@pytest.mark.parametrize("arch_id", FAMS)
def test_prefill_matches_forward(arch_id):
    cfg = _cfg(arch_id)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0), "float32")
    B, S = 1, 24
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(key, (B, S, cfg.d_model))
    if cfg.n_prefix_embeds:
        batch["prefix_embeds"] = 0.1 * jax.random.normal(
            key, (B, cfg.n_prefix_embeds, cfg.d_model))
    full = forward(cfg, params, batch, ModelCtx(kind="train"))
    cache = init_cache(cfg, B, S + 4,
                       enc_len=S if cfg.family == "encdec" else 0,
                       dtype=jnp.float32)
    lg, cache = prefill(cfg, params, batch, cache, ModelCtx(kind="prefill"))
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch_id", FAMS)
def test_decode_matches_forward(arch_id):
    """forward(tokens[:S]) position S-1 logits == prefill(tokens[:S-1]) then
    decode(token[S-1])."""
    cfg = _cfg(arch_id)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0), "float32")
    B, S = 1, 16
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    enc = jax.random.normal(key, (B, S, cfg.d_model)) \
        if cfg.family == "encdec" else None

    def mk(t):
        b = {"tokens": t}
        if enc is not None:
            b["enc_embeds"] = enc
        return b

    full = forward(cfg, params, mk(tokens), ModelCtx(kind="train"))
    cache = init_cache(cfg, B, S + 4,
                       enc_len=S if cfg.family == "encdec" else 0,
                       dtype=jnp.float32)
    _, cache = prefill(cfg, params, mk(tokens[:, :S - 1]), cache,
                       ModelCtx(kind="prefill"))
    lg, cache = decode_step(cfg, params, cache, tokens[:, S - 1:],
                            jnp.int32(S - 1), ModelCtx(kind="decode"))
    np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, -1]),
                               rtol=5e-3, atol=5e-3)
