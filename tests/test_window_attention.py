"""The window form of the ragged paged kernel (sliding-window layers,
ops/ragged_paged_attention.py) in interpret mode against a masked jnp
attention over the unpaged keys: decode and prefill shapes, rows that
straddle the window's edge, rows shorter than the window, a ring table
whose columns behind the window hold a poisoned page."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ragged_paged_attention as rpa

PAGE = 8
H, KVH, D = 8, 2, 32


def _dense(q, k, v, q_pos, window):
    """q [Q, H, D] at positions q_pos over keys k/v [S, KVH, D] at
    positions 0..S-1: key j is seen iff j <= i and i - j < window."""
    g = q.shape[1] // k.shape[1]
    kk, vv = (jnp.repeat(x, g, axis=1).astype(jnp.float32) for x in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32), kk) * D ** -0.5
    j = jnp.arange(k.shape[0])[None, :]
    i = q_pos[:, None]
    s = jnp.where(((j <= i) & (i - j < window))[None], s, -1e30)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv)


def _paged(rng, lens, ring: int, window: int, write_ahead: int = 0):
    """Keys of rows ``lens`` long in a ring table ``ring`` pages wide:
    only the pages a window-layer owner would still hold (those from the
    page of key ``len - write_ahead - window`` on) are mapped; every other
    column names page 1, which is NaN all over."""
    n_pages = 2 + sum(-(-n // PAGE) for n in lens)
    kp = np.zeros((n_pages, PAGE, KVH * D), np.float32)
    vp = np.zeros_like(kp)
    kp[1] = vp[1] = np.nan
    tables = np.ones((len(lens), ring), np.int32)
    keys, nxt = [], 2
    for r, n in enumerate(lens):
        k = rng.standard_normal((n, KVH, D)).astype(np.float32)
        v = rng.standard_normal((n, KVH, D)).astype(np.float32)
        keys.append((k, v))
        first = max(n - write_ahead - window, 0) // PAGE
        for p in range(first, -(-n // PAGE)):
            lo, hi = p * PAGE, min((p + 1) * PAGE, n)
            kp[nxt, :hi - lo] = k[lo:hi].reshape(hi - lo, -1)
            vp[nxt, :hi - lo] = v[lo:hi].reshape(hi - lo, -1)
            tables[r, p % ring] = nxt
            nxt += 1
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables), keys


@pytest.mark.parametrize("window", [16, 24])
def test_window_decode_matches_masked_attention(window):
    rng = np.random.default_rng(window)
    # shorter than the window, at its edge, one past it, many windows deep,
    # and an idle row
    lens = [5, window, window + 1, 9 * window + 3, 3 * PAGE, 0]
    ring = rpa.window_table_pages(window, PAGE, 1)
    kp, vp, tables, keys = _paged(rng, [max(n, 1) for n in lens], ring,
                                  window)
    q = jnp.asarray(rng.standard_normal((len(lens), H, D)), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    got = rpa.ragged_decode_attention(q, kp, vp, tables, lengths,
                                      interpret=True, window=window)
    ref = rpa.paged_decode_reference(
        q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), tables, lengths,
        window=window)
    for r, n in enumerate(lens):
        if n == 0:
            continue
        k, v = keys[r]
        want = _dense(q[r][None], jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray([n - 1]), window)[0]
        # float32 throughout; the streaming softmax sums in another order
        np.testing.assert_allclose(got[r], want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(ref[r], want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,chunk", [(16, 16), (24, 32)])
def test_window_prefill_matches_masked_attention(window, chunk):
    rng = np.random.default_rng(window + chunk)
    # (start, q_len): a first chunk, a chunk that straddles the window's
    # edge, a short last chunk far past it, a padding row
    rows = [(0, chunk), (chunk, chunk), (7 * chunk, chunk - 5), (0, 0)]
    ring = rpa.window_table_pages(window, PAGE, chunk)
    kp, vp, tables, keys = _paged(
        rng, [max(s + n, 1) for s, n in rows], ring, window,
        write_ahead=chunk)
    q = jnp.asarray(rng.standard_normal((len(rows), chunk, H, D)),
                    jnp.float32)
    starts = jnp.asarray([s for s, _ in rows], jnp.int32)
    q_lens = jnp.asarray([n for _, n in rows], jnp.int32)
    got = rpa.ragged_paged_attention(q, kp, vp, tables, starts, q_lens,
                                     interpret=True, window=window)
    ref = rpa.ragged_paged_reference(
        q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), tables, starts, q_lens,
        window=window)
    for r, (s, n) in enumerate(rows):
        if n == 0:
            continue
        k, v = keys[r]
        want = _dense(q[r, :n], jnp.asarray(k), jnp.asarray(v),
                      s + jnp.arange(n), window)
        np.testing.assert_allclose(got[r, :n], want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(ref[r, :n], want, atol=2e-5, rtol=2e-5)


def test_window_form_without_a_window_is_the_plain_kernel():
    """A window as long as the context masks nothing: the window form
    then gives what the plain form gives on the same (unwrapped) table."""
    rng = np.random.default_rng(3)
    lens = [40, 17]
    kp, vp, tables, _ = _paged(rng, lens, 8, window=10 ** 6)
    q = jnp.asarray(rng.standard_normal((2, H, D)), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    plain = rpa.ragged_decode_attention(q, kp, vp, tables, lengths,
                                        interpret=True)
    wide = rpa.ragged_decode_attention(q, kp, vp, tables, lengths,
                                       interpret=True, window=48)
    np.testing.assert_allclose(wide, plain, atol=1e-6, rtol=1e-6)


def test_ring_too_narrow_is_refused():
    q = jnp.zeros((1, 16, H, D))
    pool = jnp.zeros((4, PAGE, KVH * D))
    with pytest.raises(ValueError, match="ring"):
        rpa.ragged_paged_attention(
            q, pool, pool, jnp.zeros((1, 3), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
            interpret=True, window=16)
