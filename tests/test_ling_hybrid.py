"""models/ling_hybrid.py through the paged engine against the benchmark's
plain reference (benchmarks/reference/ling_hybrid_decoder.py), at a small
size in float32: a dense layer and one period (two Kimi-delta-attention
layers, one gated latent-attention layer, one more KDA layer), hidden 64,
16 experts in 4 groups, top-2 of the 2 best groups, group 0 held.

Tolerances. Logits here are ~N(0, 1.5^2). Program and reference are both
float32 and differ in the ORDER of their sums alone — the chunked form
with its sub-blocks against the token scan, the absorbed latent form over
pages against per-head keys and values, tokens grouped by expert against
every token through every expert: 2e-5 measured, 2e-4 allowed (LOGIT_TOL).
A state, a decay, a convolution tail, a rotation, a gate or a group that is
wrong moves logits by 1e-1 and more.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from ling_util import CHUNK, LOGIT_TOL, MODEL, PAGE, build, tokens

from benchmarks.reference.ling_hybrid_decoder import LingHybridDecoder
from ray_tpu.models import ling_hybrid as lh
from ray_tpu.models import qwen3_next as qn


@pytest.fixture(scope="module")
def model():
    return build()


# ---------------------------------------------------------------------------
# The forwards, table by table: logits at every position returned
# ---------------------------------------------------------------------------

def _prefill_then_decode(cfg, params, prompt, extra, dispatches):
    """tests/test_qwen3_next.py's: the prompt through ``prefill_paged_rows``
    as ``dispatches`` (each a list of (start, tokens) rows of ONE sequence
    in slot 0), then ``extra`` through ``decode_paged`` a token at a
    time."""
    caches = lh.init_paged_cache(cfg, 32, PAGE, state_slots=2,
                                 state_snapshots=1)
    table = np.zeros((1, 16), np.int32)
    table[0] = np.arange(1, 17)
    out, started = [], False
    for rows in dispatches:
        r = len(rows)
        chunks = np.zeros((r, CHUNK), np.int32)
        st = np.zeros((r, 5), np.int32)
        for i, (pos, n) in enumerate(rows):
            chunks[i, :n] = prompt[pos:pos + n]
            st[i, qn.LOAD] = 1
            st[i, qn.MODE] = qn.CHAIN if i else (
                qn.CONTINUE if started else qn.FRESH)
            st[i, qn.STORE] = 1 if i + 1 == r else 0
        started = True
        logits, caches, _ = lh.prefill_paged_rows(
            params, jnp.asarray(chunks), caches,
            (jnp.asarray(np.repeat(table, r, 0)), jnp.asarray(st)),
            jnp.asarray([p for p, _ in rows]),
            jnp.asarray([n for _, n in rows]), cfg, page_size=PAGE)
        out.append(logits[-1])
    pos = len(prompt)
    for tok in extra:
        logits, caches, _ = lh.decode_paged(
            params, jnp.asarray([[tok]]), caches,
            (jnp.asarray(table), jnp.asarray([1])), jnp.asarray([pos]),
            cfg, page_size=PAGE)
        out.append(logits[0])
        pos += 1
    return out


@pytest.mark.parametrize("n_prompt,dispatches", [
    (77, [[(0, 32)], [(32, 32)], [(64, 13)]]),
    (77, [[(0, 32), (32, 32), (64, 13)]]),
    (66, [[(0, 32), (32, 24)], [(56, 8), (64, 2)]]),
    (33, [[(0, 32), (32, 1)]]),
], ids=["a_dispatch_a_chunk", "rows_of_one_dispatch", "cut_at_pages",
        "one_token_row"])
def test_prefill_then_decode_gives_the_reference_logits(model, n_prompt,
                                                        dispatches):
    cfg, params, ref = model
    seq = tokens(n_prompt + 5, seed=n_prompt)
    want = ref.logits(params, jnp.asarray(seq))
    got = _prefill_then_decode(cfg, params, seq[:n_prompt], seq[n_prompt:],
                               dispatches)
    ends = [rows[-1][0] + rows[-1][1] - 1 for rows in dispatches] + list(
        range(n_prompt, n_prompt + 5))
    for logits, at in zip(got, ends):
        np.testing.assert_allclose(logits, want[at], atol=LOGIT_TOL, rtol=0)


def test_the_model_module_is_the_reference(model):
    """``apply`` (no cache) against the independent reference."""
    cfg, params, ref = model
    seq = jnp.asarray(tokens(96, seed=1))
    np.testing.assert_allclose(lh.apply(params, seq[None], cfg)[0],
                               ref.logits(params, seq), atol=LOGIT_TOL,
                               rtol=0)


@pytest.mark.parametrize("control", [
    {"decay": "head_mean"}, {"groups": False}, {"state_dtype": "bfloat16"},
    {"round_to": "float8_e4m3fn"}], ids=lambda c: next(iter(c)))
def test_a_control_of_the_reference_is_not_the_model(model, control):
    """Each control leaves out or degrades one mechanism: the reference
    then parts from the model by far more than the tolerance."""
    cfg, params, _ = model
    seq = jnp.asarray(tokens(96, seed=1))
    gap = np.abs(np.asarray(lh.apply(params, seq[None], cfg)[0])
                 - np.asarray(LingHybridDecoder(MODEL, **control).logits(
                     params, seq))).max()
    assert gap > 50 * LOGIT_TOL
