"""The paged cache's host side (llm/kv_cache.py) driven alone: no model,
no weights, no jit. A stub stands for the model module's cache seam
(cache_layers / cache_window / window_ring_pages / init_paged_cache); the walk below plays
the engine's part — admit, mid-prefill reuse, ensure, the bookings,
release — and checks after EVERY operation what the engine tests can only
check after whole generations."""
import copy

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.chainstats import ChainStatsTable
from ray_tpu.llm.engine import SamplingParams, _Request
from ray_tpu.llm.kv_cache import WINDOW_COUNTERS, KVCache, window_need
from ray_tpu.llm.paged_engine import PagedEngineConfig

PAGE, CHUNK, WINDOW, ROWS = 8, 16, 16, 2
KINDS = pytest.mark.parametrize("window", [0, WINDOW],
                                ids=["full", "full+window"])


class _Seam:
    """What KVCache calls of a model module."""

    def __init__(self, window):
        self.window = window

    def cache_window(self, mc):
        return self.window

    def cache_layers(self, mc):
        # a sliding layer, then a full one
        return (["window"] if self.window else []) + ["full"]

    def window_ring_pages(self, mc, page, write):
        return -(-(self.window + write) // page) + 1

    def init_paged_cache(self, mc, num_pages, page, window_pages=0):
        # a sliding layer, then a full one
        return [{"k": jnp.zeros((p, page, 4))}
                for p in ([window_pages] if self.window else []) + [num_pages]]


def _cache(window, chains=True, **kw):
    kw = dict(dict(model=None, max_batch_size=3, page_size=PAGE,
                   num_pages=40, num_window_pages=40 if window else 0,
                   max_pages_per_seq=32, chunk_size=CHUNK, decode_window=4,
                   prefill_rows=ROWS), **kw)
    cfg = PagedEngineConfig(**kw)
    stats = dict.fromkeys(("prefix_hits", "prefix_misses", "prefix_evictions",
                           "prefix_tokens_saved"), 0)
    cache = KVCache(cfg, _Seam(window), stats, ROWS)
    if cache.two_kinds:
        stats.update(dict.fromkeys(WINDOW_COUNTERS, 0))
    if chains:
        cache.log.chains = ChainStatsTable(16, 64)
    return cache


def _req(rid, ids, max_tokens=8):
    return _Request(rid, list(ids), SamplingParams(max_tokens=max_tokens))


def _tokens(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 250, n)]


def _snapshot(cache, reqs):
    """Every structure an operation could touch, copied."""
    return copy.deepcopy((
        [(k.space.free, k.space.refs, k.space.hash_to_page,
          k.space.page_to_hash, [list(t) for t in k.space.tiers], k.table)
         for k in cache.kinds],
        cache.stats, cache.log.new, cache.log.dropped, cache.log.chain_of,
        [(r.slot, r.pages, r.wpages, r.wlo, r.prefill_pos, r.chain_slot,
          r.prefix_tokens_saved) for r in reqs]))


def _same(a, b):
    if isinstance(a, np.ndarray):
        return np.array_equal(a, b)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_same, a, b))
    return a == b


def _check(cache, live):
    """The allocator's invariants, over every kind's space, against the
    requests that hold pages (``live``: slot -> request)."""
    for k in cache.kinds:
        sp = k.space
        want = np.zeros_like(sp.refs)
        for r in live.values():
            for pid in (r.pages if k is cache.full else r.wpages):
                want[pid] += 1
        # references are exactly the requests' holdings: never negative,
        # never leaked
        assert np.array_equal(sp.refs, want)
        parked = [pid for t in sp.tiers for pid in t]
        held = [int(p) for p in np.flatnonzero(sp.refs)]
        everything = sorted(sp.free + parked + held)
        # free, parked and held pages partition the space less page 0
        assert everything == list(range(1, sp.num_pages))
        assert sp.avail() == len(sp.free) + len(parked)
        assert sp.live() == len(held)
        # the index is a bijection
        assert len(sp.hash_to_page) == len(sp.page_to_hash)
        for h, pid in sp.hash_to_page.items():
            assert sp.page_to_hash[pid] == h
        # a parked page is unreferenced and published; a free one neither
        for pid in parked:
            assert sp.refs[pid] == 0 and pid in sp.page_to_hash
        for pid in sp.free:
            assert sp.refs[pid] == 0 and pid not in sp.page_to_hash
        # a slot's table names its request's pages; a free slot's is zero
        for slot, row in enumerate(k.table):
            req = live.get(slot)
            if req is None:
                assert not row.any(), (slot, k.name)
            elif k is cache.full:
                assert row[:len(req.pages)].tolist() == req.pages
                assert not row[len(req.pages):].any()
            else:   # a column behind the window may keep a stale id
                for i, pid in enumerate(req.wpages):
                    assert row[(req.wlo + i) % k.ring] == pid
    if cache.log.chains is not None:
        # every published full page is charged to exactly one chain
        assert cache.log.chains.totals()["resident_pages"] == len(
            cache.index.hash_to_page)
    if cache.two_kinds:
        st = cache.stats
        for name, k in (("full", cache.full), ("window", cache.window)):
            assert st[f"{name}_pages_claimed"] - st[
                f"{name}_pages_returned"] == sum(
                len(r.pages if k is cache.full else r.wpages)
                for r in live.values())


def _published(cache, doc, rid=0, slot=0):
    """Admit, prefill to the last whole chunk and release: doc's pages are
    published and parked, as a short answer leaves them."""
    req = _req(rid, doc + [1, 2])
    assert cache.admit(req, slot)
    for pos in range(req.prefill_pos, len(doc), CHUNK):
        assert cache.ensure(req, pos + CHUNK)
        cache.booked_prefill([(req, pos, CHUNK)])
    req.prefill_pos = len(doc)
    cache.release(req)
    return cache.hash_chain(doc)


# -- (a) a seeded walk, the invariants after every operation -----------------

@KINDS
@pytest.mark.parametrize("seed", [0, 1])
def test_walk_keeps_the_allocators_invariants(window, seed):
    cache = _cache(window, num_pages=28)     # tight: admissions wait
    cache.log.track = True
    rng = np.random.default_rng(seed)
    docs = [_tokens(int(rng.integers(40, 130)), 10 + i) for i in range(3)]
    pending, live, free_slots, rid = [], {}, [0, 1, 2], 0
    refused = admitted = reused = 0
    for _ in range(700):
        op = rng.choice(["submit", "admit", "prefill", "decode", "release"],
                        p=[0.2, 0.2, 0.3, 0.25, 0.05])
        if op == "submit" and len(pending) < 4:
            d = docs[int(rng.integers(len(docs)))]
            cut = int(rng.integers(len(d) // 2, len(d) + 1))
            pending.append(_req(rid, d[:cut] + _tokens(
                int(rng.integers(0, 6)), 1000 + rid),
                max_tokens=int(rng.integers(2, 24))))
            rid += 1
        elif op == "admit" and pending and free_slots:
            req = pending[0]
            before = _snapshot(cache, [req])
            if cache.admit(req, free_slots[0]):
                pending.pop(0)
                live[free_slots.pop(0)] = req
                admitted += 1
                assert req.prefill_pos % CHUNK == 0
                assert req.prefill_pos < len(req.prompt_ids)
                assert len(req.pages) == cache.pages_for(
                    len(req.prompt_ids) + 1)
            else:
                refused += 1
                assert _same(before, _snapshot(cache, [req]))
        elif op == "prefill":
            for req in live.values():
                if req.prefill_pos >= len(req.prompt_ids):
                    continue
                was = req.prefill_pos
                cache.reuse(req)
                reused += req.prefill_pos > was
                pos = req.prefill_pos
                n = min(CHUNK, len(req.prompt_ids) - pos)
                if cache.ensure(req, pos + n):
                    req.prefill_pos = pos + n
                    cache.booked_prefill([(req, pos, n)])
                    if req.prefill_pos >= len(req.prompt_ids):
                        req.out_ids.append(7)
                break
        elif op == "decode":
            for slot, req in list(live.items()):
                if not req.out_ids:
                    continue
                total = len(req.prompt_ids) + len(req.out_ids)
                w = int(rng.integers(1, 5))
                if len(req.out_ids) + w > req.params.max_tokens or \
                        total + w >= cache.cfg.max_seq_len or \
                        not cache.ensure(req, total + w):
                    cache.release(req)
                    del live[slot]
                    free_slots.append(slot)
                    req.slot = -1
                    continue
                req.out_ids += _tokens(w, total)
                cache.advanced(req, total + w - 1)
                assert cache.held(req) * PAGE >= total + w
        elif op == "release" and live:
            slot = int(rng.choice(list(live)))
            req = live.pop(slot)
            cache.release(req)
            free_slots.append(slot)
            req.slot = -1
            assert not req.pages and not req.wpages and req.wlo == 0
        _check(cache, live)
    st = cache.stats
    # the walk reached what it is for: hits, evictions, refusals, reuse
    assert admitted > 20 and refused > 0, (admitted, refused)
    assert reused > 0
    assert st["prefix_hits"] > 0 and st["prefix_evictions"] > 0
    if window:
        assert st["window_pages_returned"] > 0
    new, dropped = cache.log.drain()
    assert set(new) <= set(cache.index.hash_to_page)
    assert not set(dropped) & set(cache.index.hash_to_page)


# -- (b) a refused admission -------------------------------------------------

@KINDS
def test_a_refused_admission_leaves_every_structure_as_it_was(window):
    cache = _cache(window, num_pages=24)
    doc, holder = _tokens(6 * CHUNK, 1), _req(1, _tokens(80, 2))
    # publish the document, then let another request hold the free pages
    _published(cache, doc)
    assert cache.admit(holder, 1)
    follow = _req(2, doc + [4, 5, 6, 7])
    cache.prompt_hashes(follow)      # a pure function of the prompt
    assert cache.match(follow)       # a hit that the pool cannot seat
    before = _snapshot(cache, [follow, holder])
    assert not cache.admit(follow, 2)
    assert _same(before, _snapshot(cache, [follow, holder]))
    assert follow.slot == -1 and not follow.pages and not follow.wpages
    # with the pool given back it is admitted through its cached prefix
    cache.release(holder)
    assert cache.admit(follow, 2) and follow.prefill_pos > 0


# -- (c) the window tail and the cold tier -----------------------------------

@pytest.mark.parametrize("gone,counter,saved", [
    ("last_chunk", "prefix_tail_cut", 5 * CHUNK),
    ("all", "prefix_tail_lost", 0),
    ("none", None, 6 * CHUNK)])
def test_a_prefix_is_cut_to_the_longest_that_has_its_window_tail(
        gone, counter, saved):
    cache = _cache(WINDOW)
    doc = _tokens(6 * CHUNK, 3)
    hashes = _published(cache, doc)
    sp = cache.window.space
    lose = {"last_chunk": hashes[5 * CHUNK // PAGE:], "all": hashes,
            "none": []}[gone]
    for h in lose:
        pid = sp.hash_to_page.get(h)
        if pid is not None:
            sp.unpark(pid)
            sp.forget(pid)
            sp.free.append(pid)
    ask = _req(1, doc + [9, 8, 7])
    assert len(cache.match(ask)) == 6 * CHUNK // PAGE   # the full layers': all
    assert cache.admit(ask, 1)
    assert ask.prefill_pos == ask.prefix_tokens_saved == saved
    st = cache.stats
    assert st["prefix_tail_cut"] + st["prefix_tail_lost"] == (gone != "none")
    if counter:
        assert st[counter] == 1
        assert st["prefix_tail_tokens_lost"] == 6 * CHUNK - saved
    # the tail is pinned as the request's own window pages, in its ring
    tail = -(-(WINDOW - 1) // PAGE) if saved else 0
    assert len(ask.wpages) == tail and ask.wlo == saved // PAGE - tail
    assert all(sp.refs[pid] == 1 for pid in ask.wpages)
    _check(cache, {1: ask})


def test_a_cold_page_is_reclaimed_before_a_warm_one():
    cache = _cache(WINDOW, num_window_pages=32)
    kind, sp = cache.window, cache.window.space
    req = _req(0, _tokens(12 * CHUNK, 4))
    # the rule: a sequence's first two windows and what lies too far behind
    # its prompt's end are cold; the tail of a follow-up's prefix is warm
    last = len(req.prompt_ids) // PAGE - 1
    assert kind.cold(req, 0) and kind.cold(req, 2 * WINDOW // PAGE - 1)
    assert kind.cold(req, last - 2 * WINDOW // PAGE - 1)
    assert not kind.cold(req, last) and not kind.cold(req, last - 1)
    # the tiers: a warm page parked FIRST still outlives a cold one
    warm, cold = sp.take(), sp.take()
    sp.publish(warm, b"warm")
    sp.publish(cold, b"cold")
    sp.unpin(warm)
    sp.unpin(cold, cold=True)
    held = [sp.take() for _ in range(len(sp.free))]
    assert sp.avail() == 2 and not sp.free
    assert sp.take() == cold and cache.stats["window_evictions"] == 1
    assert b"cold" not in sp.hash_to_page and b"warm" in sp.hash_to_page
    assert sp.take() == warm and b"warm" not in sp.hash_to_page
    assert sp.avail() == 0 and len(held) == 29


# -- (d) what a release leaves -----------------------------------------------

@KINDS
def test_a_release_leaves_the_slots_tables_zero(window):
    cache = _cache(window)
    req = _req(0, _tokens(75, 5))
    assert cache.admit(req, 2)
    assert cache.ensure(req, 75 + 9)
    assert all(k.table[2].any() for k in cache.kinds)
    pair = cache.tables([2, -1], 16)
    full = pair[0] if window else pair
    assert full.shape == (2, 16) and full[0, :len(req.pages)].tolist() == \
        req.pages[:16] and not full[1].any()
    if window:
        assert pair[1].shape == (2, cache.window.ring) and not pair[1][1].any()
    cache.release(req)
    assert not any(k.table.any() for k in cache.kinds)
    assert all(k.space.live() == 0 for k in cache.kinds)
    zero = cache.tables([2, 0], 4)
    assert not any(t.any() for t in (zero if window else [zero]))
    _check(cache, {})


# -- construction, and the index calls of export / import / the spill tier ---

def test_the_window_pool_says_what_it_needs():
    cfg = PagedEngineConfig(model=None, max_batch_size=3, page_size=PAGE,
                            chunk_size=CHUNK, decode_window=4)
    assert window_need(cfg, _Seam(0), ROWS) == (0, 0)
    ring, need = window_need(cfg, _Seam(WINDOW), ROWS)
    # a window and a dispatch's write, in pages, and one for the two ends
    assert ring == (WINDOW + ROWS * CHUNK) // PAGE + 1
    assert need == 3 * ring + 2 * (ROWS * CHUNK // PAGE) + 1
    with pytest.raises(ValueError, match=f"need {need} pages"):
        _cache(WINDOW, num_window_pages=need - 1)
    assert _cache(WINDOW, num_window_pages=need).window.ring == ring
    with pytest.raises(ValueError, match="sliding-window"):
        _cache(0, num_window_pages=8)
    two = _cache(WINDOW)
    assert two.layer_kinds == ["window", "full"] and two.two_kinds
    assert _cache(0).layer_kinds == ["full"] and not _cache(0).two_kinds


def test_adopted_pages_keep_a_reserve_and_park_published():
    cache = _cache(0, num_pages=12)
    hashes = cache.hash_chain(_tokens(10 * PAGE, 6))
    cache.park(cache.take_unheld(hashes[1:2], 0), hashes[1:2], chain=-1)
    took = cache.take_unheld(hashes, reserve=4)
    # 11 pages less the reserve; the hash the cache holds is skipped
    assert [i for i, _ in took] == [0, 2, 3, 4, 5, 6, 7]
    assert cache.index.avail() == 4
    cache.park(took, hashes, chain=-1)
    assert len(cache.index.run(hashes)) == 8
    assert [h in cache.index.hash_to_page for h in hashes[:9]] == \
        [True] * 8 + [False]
    assert cache.pool_stats() == {"free_pages": 3, "cached_pages": 8,
                                  "total_pages": 12}
    assert all(cache.index.refs[pid] == 0 for _, pid in took)


def test_the_log_hears_of_the_full_index_alone():
    cache = _cache(WINDOW)
    heard = []
    cache.log.demote = lambda pid, h, slot: heard.append((pid, h, slot))
    cache.log.track = True
    doc = _tokens(4 * CHUNK, 7)
    hashes = _published(cache, doc)
    new, dropped = cache.log.drain()
    assert new == hashes and not dropped
    assert cache.log.drain() == ([], [])
    for kind in cache.kinds:
        sp = kind.space
        while sp.free:
            sp.take()
        sp.take()           # reclaims the oldest parked page
    # the full pages' eviction reached the demote hook and the delta log;
    # the window pages' reached neither
    assert [h for _, h, _ in heard] == [hashes[0]]
    assert cache.log.drain() == ([], [hashes[0]])
    assert cache.stats["prefix_evictions"] == 1
    assert cache.stats["window_evictions"] == 1
