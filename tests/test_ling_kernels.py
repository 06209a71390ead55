"""ops/gated_delta.py with a decay a key CHANNEL (Kimi delta attention)
against the token-by-token scan, in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta as gd


def _kda_inputs(r, c, nk, nv, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (l2(jax.random.normal(ks[0], (r, c, nk, dk))) * dk ** -0.5,
            l2(jax.random.normal(ks[1], (r, c, nk, dk))),
            jax.random.normal(ks[2], (r, c, nv, dv)),
            # from forgetting in a token to carrying hundreds
            -5.0 * jax.nn.sigmoid(3 * jax.random.normal(
                ks[3], (r, c, nv, dk)) - 3),
            jax.nn.sigmoid(jax.random.normal(ks[4], (r, c, nv))),
            jax.random.normal(ks[5], (r, nv, dk, dv)))


@pytest.mark.parametrize("c", [128, 64, 32])
def test_the_chunked_kernel_with_a_decay_a_channel_is_the_scan(c):
    """tests/test_qwen3_next.py's case with ``g`` [.., nv, dk]: non-zero
    initial states, a short row, a pad row, rows chained across chunk
    boundaries and not. 1e-5 measured, 1e-4 allowed."""
    q, k, v, g, beta, s0 = _kda_inputs(4, c, 2, 4, 32, 32, seed=c)
    lens = jnp.asarray([c, c // 3, c, 0])
    live = (jnp.arange(c)[None, :] < lens[:, None])[..., None]
    g, beta = jnp.where(live[..., None], g, 0.0), jnp.where(live, beta, 0.0)
    chain = jnp.asarray([0, 1, 0, 1])
    want_o, want_s = gd.gated_delta_rows_reference(q, k, v, g, beta, s0,
                                                   chain)
    got_o, got_s = gd.gated_delta_prefill(q, k, v, g, beta, s0, chain,
                                          interpret=True)
    np.testing.assert_allclose(jnp.where(live[..., None], got_o, 0),
                               jnp.where(live[..., None], want_o, 0),
                               atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_s[3], got_s[2], atol=0, rtol=0)


def test_the_decay_at_its_bound_for_a_whole_chunk():
    """``g`` = -5 in every channel for 64 tokens running, at the published
    key width: against ONE reference a chunk the keys' factor would reach
    exp(315) (float32 ends at exp(88.7)); with a reference a 16-token
    sub-block, taken at its middle row, every factor stays within
    exp(+-40). No inf, no nan, and the scan's numbers — the outputs at
    this decay are ~3e-2, so 1e-6 is the scan itself."""
    q, k, v, _, beta, s0 = _kda_inputs(2, 64, 1, 2, 128, 128, seed=5)
    g = jnp.full((2, 64, 2, 128), -5.0)
    chain = jnp.asarray([0, 1])
    want_o, want_s = gd.gated_delta_rows_reference(q, k, v, g, beta, s0,
                                                   chain)
    got_o, got_s = gd.gated_delta_prefill(q, k, v, g, beta, s0, chain,
                                          interpret=True)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o, atol=1e-6, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6, rtol=0)


def test_a_decay_constant_over_the_channels_is_the_scalar_form():
    """GDN is the case ``g`` constant over dk: the per-channel kernels on
    such a decay give what the scalar kernels give on its one value, to
    the order of their sums (a cumulative sum as a product with a triangle
    against a masked reduction; the sub-blocks): 1e-5 allowed."""
    q, k, v, g, beta, s0 = _kda_inputs(2, 128, 2, 4, 32, 32, seed=2)
    scalar = g[..., 0]
    g = jnp.broadcast_to(scalar[..., None], g.shape)
    chain = jnp.asarray([0, 1])
    want_o, want_s = gd.gated_delta_prefill(q, k, v, scalar, beta, s0, chain,
                                            interpret=True)
    got_o, got_s = gd.gated_delta_prefill(q, k, v, g, beta, s0, chain,
                                          interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5, rtol=0)
    states = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 32, 32))
    rows = jnp.asarray([3, 1])
    step = (q[0, :2], k[0, :2], v[0, :2])
    want = gd.gated_delta_decode(states, rows, *step, scalar[0, :2],
                                 beta[0, :2], interpret=True)
    got = gd.gated_delta_decode(states, rows, *step, g[0, :2], beta[0, :2],
                                interpret=True)
    # the decode update multiplies by the same factor either way; what
    # differs is how the compiler contracts a multiply and an add
    np.testing.assert_allclose(got[0], want[0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(got[1], want[1], atol=1e-6, rtol=0)


def test_the_decode_update_with_a_decay_a_channel_is_the_scan():
    q, k, v, g, beta, _ = _kda_inputs(1, 5, 2, 4, 32, 32, seed=9)
    states = jax.random.normal(jax.random.PRNGKey(1), (6, 4, 32, 32))
    rows = jnp.asarray([3, 0, 1, 5, 0])      # two idle rows on the sink
    want_o, want_s = gd.gated_delta_decode(states, rows, q[0], k[0], v[0],
                                           g[0], beta[0])
    got_o, got_s = gd.gated_delta_decode(states, rows, q[0], k[0], v[0],
                                         g[0], beta[0], interpret=True)
    live = np.asarray([0, 2, 3])
    np.testing.assert_allclose(got_o[live], want_o[live], atol=1e-5, rtol=0)
    np.testing.assert_allclose(got_s[1:], want_s[1:], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(got_s[jnp.asarray([2, 4])],
                                  states[jnp.asarray([2, 4])])
