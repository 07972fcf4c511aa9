"""int8 KV cache: kernel-vs-oracle + quantized decode path vs bf16 decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.kernels import ref
from repro.kernels.quant_decode import quant_decode_attention, quantize_kv
from repro.models import (ModelCtx, decode_step, init_cache, init_params,
                          model_specs, prefill)
from repro.models.decode import quantize_cache


@pytest.mark.parametrize("b,h,kv,s,d", [(1, 4, 4, 256, 64), (2, 8, 2, 512, 64)])
def test_kernel_matches_oracle(b, h, kv, s, d):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, s, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, s, d), jnp.bfloat16)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    got = quant_decode_attention(q, k8, ks, v8, vs, s - 7, block_s=128,
                                 interpret=True)
    want = ref.quant_decode_ref(q, k8, ks, v8, vs, s - 7)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("pos", [[1, 128, 129, 384], [512, 7, 255, 256]])
def test_kernel_matches_oracle_per_row_pos(pos):
    """Continuous batching: each row attends to its own prefix. Rows end
    inside, at and just past a 128-slot block, so skipped and clamped
    blocks are covered, and a row of length 1 sees only slot 0."""
    b, h, kv, s, d = 4, 6, 2, 512, 64
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (b, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, s, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, s, d), jnp.bfloat16)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    pos = jnp.asarray(pos, jnp.int32)
    got = quant_decode_attention(q, k8, ks, v8, vs, pos, block_s=128,
                                 interpret=True)
    want = ref.quant_decode_ref(q, k8, ks, v8, vs, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2,
                               rtol=2e-2)
    # each row equals that row decoded alone at its own scalar length
    for i in range(b):
        one = ref.quant_decode_ref(q[i:i + 1], k8[i:i + 1], ks[i:i + 1],
                                   v8[i:i + 1], vs[i:i + 1], int(pos[i]))
        np.testing.assert_array_equal(np.asarray(want[i:i + 1]),
                                      np.asarray(one))


@pytest.mark.parametrize("layer", [0, 2])
def test_stacked_pool_matches_layer_slice(layer):
    """The kernel over the stacked pool [L,B,KV,S,Dh] at a traced layer
    index equals the one-layer call on that layer's slice, bit for bit.
    Rows: an empty slot (pos 1), one ending inside a tile, one at a tile's
    end and a full one (pos = S)."""
    n_layers, b, h, kv, s, d = 3, 4, 8, 2, 512, 64
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (b, h, d), jnp.bfloat16)
    k8, ks = quantize_kv(jax.random.normal(jax.random.fold_in(key, 1),
                                           (n_layers, b, kv, s, d)))
    v8, vs = quantize_kv(jax.random.normal(jax.random.fold_in(key, 2),
                                           (n_layers, b, kv, s, d)))
    pos = jnp.asarray([1, 200, 384, s], jnp.int32)
    stacked = jax.jit(lambda q, k8, ks, v8, vs, pos, layer:
                      quant_decode_attention(q, k8, ks, v8, vs, pos,
                                             layer=layer, block_s=128,
                                             interpret=True))
    got = stacked(q, k8, ks, v8, vs, pos, jnp.int32(layer))
    want = quant_decode_attention(q, k8[layer], ks[layer], v8[layer],
                                  vs[layer], pos, block_s=128,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantization_error_small():
    key = jax.random.PRNGKey(1)
    k = jax.random.normal(key, (2, 2, 128, 64), jnp.float32)
    k8, ks = quantize_kv(k)
    back = k8.astype(jnp.float32) * ks[..., None]
    err = np.abs(np.asarray(back - k)).max()
    assert err < np.abs(np.asarray(k)).max() / 100   # <1% of range


def test_quantized_decode_close_to_bf16():
    cfg = reduced(get_arch("granite-20b"), dtype="float32")
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0), "float32")
    B, S = 1, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    cache0 = init_cache(cfg, B, S + 4, dtype=jnp.float32)
    _, cache0 = prefill(cfg, params, {"tokens": tokens[:, :S - 1]}, cache0,
                        ModelCtx(kind="prefill"))
    outs = {}
    for quant in (False, True):
        cache = cache0
        if quant:   # the prefilled cache in the int8 pool's layout, as
            cache = quantize_cache(cache0)          # the serve engine makes it
        lg, _ = decode_step(cfg, params, cache, tokens[:, S - 1:],
                            jnp.int32(S - 1), ModelCtx(kind="decode"))
        outs[quant] = np.asarray(lg, np.float32)
    # int8 cache changes logits only at quantization-noise level
    scale = np.abs(outs[False]).max()
    assert np.abs(outs[True] - outs[False]).max() < 0.05 * scale
