"""The plain reference against models/llama.py at toy widths on the CPU,
and the FLOP arithmetic against counts made by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops
from benchmarks.models.llama_dense import Builder
from benchmarks.reference.dense_decoder import DenseDecoder

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256, max_position_embeddings=128, rope_theta=1e6,
            rms_norm_eps=1e-5, torch_dtype="float32", sliding_window=None,
            tie_word_embeddings=False)


def test_reference_logits_match_the_program():
    from ray_tpu.models import llama
    b = Builder(TINY, remat=False, use_flash=False)
    params = b.init_params(seed=7)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 48), np.int32)
    want = llama.apply(params, jnp.asarray(tokens), b.cfg)
    ref = DenseDecoder(TINY)
    got = jnp.stack([ref.logits(params, tokens[i]) for i in range(2)])
    # float32 on both sides: only summation order differs
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    loss = llama.cross_entropy_loss(want[:, :-1], jnp.asarray(tokens[:, 1:]))
    np.testing.assert_allclose(ref.loss(params, jnp.asarray(tokens)), loss,
                               rtol=1e-5)


def test_reference_gradient_matches_the_program():
    from ray_tpu.models import llama
    b = Builder(TINY, remat=False, use_flash=False)
    params = b.init_params(seed=1)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 256, (1, 33), np.int32))

    def prog(p):
        return llama.cross_entropy_loss(
            llama.apply(p, tokens[:, :-1], b.cfg), tokens[:, 1:])
    g1 = jax.grad(prog)(params)
    g2 = jax.grad(lambda p: DenseDecoder(TINY).loss(p, tokens))(params)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, c, atol=1e-5, rtol=1e-3)


def test_flops_by_hand():
    m = dict(hidden_size=4096, intermediate_size=14336, num_hidden_layers=4,
             num_attention_heads=32, num_key_value_heads=8, head_dim=128,
             vocab_size=32768)
    # one layer: q 4096x4096, k and v 4096x1024 each, o 4096x4096,
    # gate/up/down 3 x 4096x14336
    layer = 16777216 + 2 * 4194304 + 16777216 + 3 * 58720256
    assert layer == 218103808
    assert flops.layer_matmul_params(m) == layer
    assert flops.matmul_params(m) == 4 * layer + 4096 * 32768
    # a cached token: K and V, 4 layers x 8 KV heads x 128, bf16
    assert flops.kv_bytes_per_token(m) == 2 * 4 * 8 * 128 * 2
    # causal attention forward of one layer, one 4096-token sequence:
    # QK^T and PV, 2 FLOPs per multiply-add, half the square
    assert flops.attention_fwd_flops(m, 1, 4096) == \
        2 * 2 * 32 * 4096 * 4096 * 128 / 2
    per_tok = flops.train_flops_per_token(m, 4096)
    assert per_tok == pytest.approx(
        6 * (4 * layer + 4096 * 32768)
        + 3 * 4 * (2 * 2 * 32 * 4096 * 128 / 2))
    # the embedding table is a lookup: total > matmul by vocab x hidden + norms
    assert flops.total_params(m) - flops.matmul_params(m) == \
        32768 * 4096 + 4 * 2 * 4096 + 4096


def test_roofline_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_min_s(197e12, 1.0, peak) == (1.0, "compute")
    assert flops.roofline_min_s(1.0, 819e9, peak) == (1.0, "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("_source")
