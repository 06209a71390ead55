"""Model-zoo tests: llama (training fwd, paged-cache consistency, grads,
sharded pjit forward) and resnet."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, resnet
from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
from ray_tpu.parallel.sharding import logical_sharding


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_llama_forward_shapes(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.apply(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_decode_matches_full_forward(tiny):
    """Prefill+decode through the paged KV cache must equal the full
    forward: prefill_paged_chunk over the first 8 tokens, decode_paged
    one token at a time for the rest."""
    cfg, params = tiny
    s, page = 12, 8
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (1, s)))
    full = llama.apply(params, tokens, cfg)

    caches = llama.init_paged_cache(cfg, num_pages=5, page_size=page)
    bt = jnp.asarray([1, 2, 3, 4], jnp.int32)      # page 0 is the sink
    logits_p, caches, _ = llama.prefill_paged_chunk(
        params, tokens[:, :8], caches, bt, jnp.int32(0), cfg,
        page_size=page)
    step_logits = [logits_p[None]]
    for i in range(8, s):
        lg, caches, _ = llama.decode_paged(
            params, tokens[:, i:i + 1], caches, bt[None],
            jnp.asarray([i], jnp.int32), cfg, page_size=page)
        step_logits.append(lg[:, None])
    stitched = jnp.concatenate(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(stitched), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_llama_loss_and_grads(tiny):
    cfg, params = tiny
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 16)))

    def loss_fn(p):
        logits = llama.apply(p, tokens[:, :-1], cfg)
        return llama.cross_entropy_loss(logits, tokens[:, 1:])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    # embedding grad must be nonzero
    assert float(jnp.abs(grads["embed"]).sum()) > 0


def test_llama_sharded_forward_tp_fsdp(tiny):
    """pjit the forward over a dp×fsdp×tp mesh with param shardings from
    logical_axes; result must match the unsharded forward."""
    cfg, params = tiny
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (4, 16)))
    want = llama.apply(params, tokens, cfg)
    with use_mesh(mesh):
        shardings = logical_sharding(llama.logical_axes(cfg), mesh)
        sharded_params = jax.device_put(params, shardings)
        f = jax.jit(lambda p, t: llama.apply(p, t, cfg))
        got = f(sharded_params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_llama_param_count_8b():
    cfg = llama.llama3_8b()
    n = cfg.num_params()
    assert 7.9e9 < n < 8.2e9  # llama-3-8B ≈ 8.03B


@pytest.mark.slow
def test_resnet18_forward_and_train_step():
    cfg = resnet.resnet18()
    variables = resnet.init(jax.random.PRNGKey(0), cfg)
    images = jnp.zeros((4, 32, 32, 3))
    logits = resnet.apply(variables, images, cfg)
    assert logits.shape == (4, 10)
    logits2, new_state = resnet.apply_train(variables, images, cfg)
    assert logits2.shape == (4, 10)
    assert "batch_stats" in new_state


@pytest.mark.slow
def test_remat_save_attn_matches_full():
    """The save_attn remat policy must not change gradients."""
    import dataclasses
    base = llama.llama_tiny(n_layers=2, dim=64, mlp_dim=128, n_heads=4,
                            n_kv_heads=2, max_seq_len=128)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, base.vocab_size, (2, 33)), jnp.int32)
    grads = {}
    for pol in ("full", "save_attn"):
        cfg = dataclasses.replace(base, remat=True, remat_policy=pol,
                                  use_flash=True)
        params = llama.init(jax.random.PRNGKey(0), cfg)

        def loss(p, cfg=cfg):
            return llama.cross_entropy_loss(
                llama.apply(p, toks[:, :-1], cfg), toks[:, 1:])
        grads[pol] = jax.grad(loss)(params)
    for g1, g2 in zip(jax.tree_util.tree_leaves(grads["full"]),
                      jax.tree_util.tree_leaves(grads["save_attn"])):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)
