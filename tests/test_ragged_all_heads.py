"""The all-heads body of the ragged kernel (decode, the smallest verify
windows) at every key block `window_step` can derive for it (PR 48: 128
to 1,024 keys), interpret mode, against `ragged_paged_reference`.

Four tests, each a loop over its block widths and head forms, and not
one parametrised case a width and form as tests/test_ragged_attention.py
holds the per-kv-head body: this file's ~35 interpreter compiles take a
minute, and under the suite's `--dist loadfile` a file is queued by its
count of tests — with 35 it would run in the first minutes beside
tests/test_serve_frontdoor.py and tests/test_data_streaming.py, whose
store-drain checks are sensitive to what shares the machine then (four
whole runs of four lost one of them, the seed's tree none; CHANGES.md,
PR 48). A failure names its case in the assertion's message."""
import numpy as np
import jax.numpy as jnp

from ray_tpu.ops import ragged_paged_attention as rpa
from ray_tpu.ops.ragged_paged_attention import ragged_paged_reference
from test_ragged_attention import _WIDTHS, _per_head_case

# (query heads, kv heads, head size): doc-QA's and Mellum's groups, OLMoE's
# sixteen kv heads without groups, Qwen3-Next's two kv heads of 256
_FORMS = {"32_8": (32, 8, 64), "16_16": (16, 16, 64),
          "16_2_of_256": (16, 2, 256)}


def _check(case, heads, seed, table, starts, q_lens, window, block_keys):
    """One all-heads call at ``block_keys`` over pages of 16 whose sink is
    NaN: finite and the oracle's at every live query."""
    h, kvh, _ = heads
    assert rpa._all_heads(window, h // kvh), case
    args, kw, want = _per_head_case(
        "gqa", np.random.RandomState(seed), table, starts, q_lens, window,
        page=16, heads=heads)
    got = np.asarray(rpa._ragged_call(
        *args, q_tile=window, block_keys=block_keys, interpret=True, **kw))
    live = np.arange(window)[None, :] < np.asarray(q_lens)[:, None]
    assert np.isfinite(got[live]).all(), case
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=2e-5,
                               rtol=1e-5, err_msg=str(case))


def _decode_rows(ends):
    return [max(e - 1, 0) for e in ends], [min(e, 1) for e in ends]


def test_all_heads_body_at_every_block_width():
    """Decode rows (query window 1) over a 160-page table whose sweeps
    end: exactly with a block at every width (2,048 keys, row 0), inside
    a block behind clamped duplicates of the last live page (rows 1, 2:
    1,200 and 1,013 keys — the second one key into its last page), inside
    the first block (rows 3, 5: 1 and 130 keys), and a pad row between
    them (row 4). The sink behind every table's tail is NaN: a key past
    the clamp would show."""
    starts, q_lens = _decode_rows([2048, 1200, 1013, 1, 0, 130])
    for form, heads in sorted(_FORMS.items()):
        for block_keys in _WIDTHS:
            _check((form, block_keys), heads, 21, 160, starts, q_lens, 1,
                   block_keys)


def test_all_heads_body_under_a_table_narrower_than_a_block():
    """A 6-page table (96 keys) under blocks of 128 to 1,024: one grid
    step whose copies are clamped to the row's last live page."""
    starts, q_lens = _decode_rows([96, 37, 0, 16])
    for form, heads in sorted(_FORMS.items()):
        for block_keys in _WIDTHS:
            _check((form, block_keys), heads, 22, 6, starts, q_lens, 1,
                   block_keys)


def test_all_heads_verify_window_at_every_block_width():
    """Verify windows under the all-heads line (2 queries at groups 4, 5
    without groups): the causal edge inside the window falls inside a
    wide block, at a start off a page's edge, on a block's edge, in the
    first block, and in a row shorter than its window."""
    for form, window in (("32_8", 2), ("16_16", 5)):
        starts = [1021, 1024 - window, 3, 300, 0]
        q_lens = [window, window, window, window - 1, 0]
        for block_keys in _WIDTHS:
            _check((form, window, block_keys), _FORMS[form], 23, 96, starts,
                   q_lens, window, block_keys)


def test_all_heads_body_never_meets_what_its_copies_left_out():
    """The all-heads body copies a partly live block's live 128-key groups
    alone and zeroes the rest of V: a short row (37 keys, 1 key — an idle
    decode row's sweep) behind a row whose own values are NaN in every
    page finds both buffer slots full of that row's NaN, scores its block
    under probabilities of 0 there, and stays finite and right."""
    ends = [2048, 37, 1, 300]
    args, kw, _ = _per_head_case(
        "gqa", np.random.RandomState(24), 160, [e - 1 for e in ends],
        [1] * 4, 1, page=16, heads=(32, 8, 64))
    q, kp, vp, bt, starts, q_lens = args
    vp = vp.at[np.asarray(bt)[0, :128]].set(jnp.nan)    # row 0's pages
    want = ragged_paged_reference(q, kp.at[0].set(0.0), vp.at[0].set(0.0),
                                  bt, starts, q_lens)
    for block_keys in _WIDTHS[1:]:
        got = np.asarray(rpa._ragged_call(
            q, kp, vp, bt, starts, q_lens, q_tile=1, block_keys=block_keys,
            interpret=True, **kw))
        assert np.isnan(got[0]).all() and np.isfinite(got[1:]).all(), \
            block_keys
        np.testing.assert_allclose(got[1:], np.asarray(want)[1:], atol=2e-5,
                                   rtol=1e-5, err_msg=str(block_keys))
