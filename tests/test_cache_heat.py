"""Cache heat plane (llm/chainstats.py + the cluster surfaces):
per-chain stats bounded-memory guarantees, counter-verification against
the engine aggregates, on/off bit-equality (observation only — no
policy change), directory heat-entry staleness, and the head's
cache_report / cli cache renderers."""
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.chainstats import OVERFLOW_LABEL, ChainStatsTable
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama

TINY = llama.llama_tiny(vocab_size=258, max_seq_len=640)


def _cfg(**kw):
    defaults = dict(model=TINY, max_batch_size=4, page_size=8,
                    num_pages=128, max_pages_per_seq=16, chunk_size=16,
                    enable_prefix_caching=True)
    defaults.update(kw)
    return PagedEngineConfig(**defaults)


def _prompt(n, seed=0):
    return list(np.random.RandomState(seed).randint(1, 250, (n,)))


def _drain(eng, reqs):
    while not all(r.done for r in reqs):
        eng.step()


# ------------------------------------------------------------------ #
# table unit: hard cardinality cap + byte ceiling
# ------------------------------------------------------------------ #

def test_chain_table_cardinality_bound_unit():
    t = ChainStatsTable(slots=4, page_bytes=1024)
    ceiling = t.stats()["max_bytes"]
    heads = [bytes([i]) * 16 for i in range(50)]
    slots = [t.slot_for(h, b"\x01") for h in heads]
    # first 4 chains get dedicated slots; the rest fold into overflow
    assert slots[:4] == [1, 2, 3, 4]
    assert all(s == 0 for s in slots[4:])
    assert t.stats()["tracked"] == 4
    assert t.stats()["overflow_assignments"] == 46
    # established chains keep exact counts under overflow pressure
    t.hit(slots[0], pages=3, tokens=24)
    t.hit(slots[0], pages=2, tokens=16)
    for s in slots[4:]:
        t.hit(s, pages=1)
    assert int(t.hits[slots[0]]) == 5
    assert int(t.tokens_saved[slots[0]]) == 40
    assert int(t.hits[0]) == 46
    # re-lookup is stable, never reassigns
    assert t.slot_for(heads[0]) == slots[0]
    assert t.slot_for(heads[40]) == 0
    assert t.peek(heads[2]) == slots[2]
    assert t.peek(b"never-seen-----!") == 0
    # memory ceiling is fixed at construction: unbounded distinct
    # chains changed NOTHING about it
    assert t.stats()["max_bytes"] == ceiling
    # the overflow row surfaces in top() whenever it absorbed traffic
    rows = t.top(2)
    assert rows[-1]["chain"] == OVERFLOW_LABEL
    assert rows[0]["hits"] == 5
    # totals() == sum of everything including the sink
    assert t.totals()["hits"] == 5 + 46


def test_chain_table_rejects_bad_config():
    with pytest.raises(ValueError):
        _cfg(chain_stats_slots=-1)
    with pytest.raises(ValueError):
        _cfg(chain_stats_top_k=0)


# ------------------------------------------------------------------ #
# engine integration: counter-verification + overflow under traffic
# ------------------------------------------------------------------ #

def _assert_table_matches_stats(eng):
    t, st = eng.chains.totals(), eng.stats
    assert t["hits"] == st["prefix_hits"]
    assert t["misses"] == st["prefix_misses"]
    assert t["evictions"] == st["prefix_evictions"]
    assert t["tokens_saved"] == st["prefix_tokens_saved"]
    assert t["imported_pages"] == st["prefix_imported_pages"]
    assert t["exported_pages"] == st["prefix_exported_pages"]
    # resident attribution: every registered (hash-published) page is
    # charged to exactly one chain
    assert t["resident_pages"] == len(eng.cache.full.space.hash_to_page)


def test_engine_chain_attribution_counter_verified():
    """Mixed warm/evict workload: every aggregate stats bump has exactly
    one chain attribution — no double count, no drift."""
    eng = PagedInferenceEngine(_cfg(num_pages=24))
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    shared = _prompt(64, seed=7)
    for i in range(10):
        r = eng.submit(shared + _prompt(48, seed=100 + i), sp)
        _drain(eng, [r])
    assert eng.stats["prefix_hits"] > 0
    assert eng.stats["prefix_evictions"] > 0
    _assert_table_matches_stats(eng)
    # the shared chain is the hottest tracked row
    rows = eng.chains.top(3)
    assert rows[0]["hits"] == eng.stats["prefix_hits"]
    assert rows[0]["tenant"] == "base"
    assert rows[0]["last_hit_age_s"] is not None
    # accounting source parity: pool_stats derives from the same dict
    acct = eng.prefix_accounting()
    pool = eng.pool_stats()
    assert pool["prefix_hit_rate"] == acct["hit_rate"]
    assert pool["cached_pages"] == acct["cached_pages"]
    assert pool["prefix_hits"] == acct["hits"]
    assert pool["prefix_evictions"] == acct["evictions"]


def test_engine_overflow_sink_bounds_cardinality():
    """Unbounded distinct prompts: the table tracks exactly `slots`
    chains; everything else (assignments AND later evictions of never-
    learned pages) folds into __overflow__ — totals still exact."""
    eng = PagedInferenceEngine(
        _cfg(num_pages=24, chain_stats_slots=3))
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    for i in range(12):
        r = eng.submit(_prompt(48, seed=500 + i), sp)
        _drain(eng, [r])
    st = eng.chains.stats()
    assert st["tracked"] == 3
    assert st["overflow_assignments"] >= 9
    assert eng.stats["prefix_evictions"] > 0
    _assert_table_matches_stats(eng)
    # overflow row carries the folded churn
    rows = eng.chains.top(16)
    assert rows[-1]["chain"] == OVERFLOW_LABEL
    assert int(eng.chains.evictions[0]) > 0


def test_heat_plane_on_off_bit_equality():
    """Observation only: identical greedy outputs and identical
    prefix-cache aggregates with the table enabled vs disabled."""
    import dataclasses
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    shared = _prompt(96, seed=3)
    prompts = [shared + _prompt(24, seed=900 + i) for i in range(6)]

    def run(slots):
        eng = PagedInferenceEngine(
            _cfg(num_pages=32, chain_stats_slots=slots), rng_seed=0)
        outs = []
        for p in prompts:
            r = eng.submit(p, sp)
            _drain(eng, [r])
            outs.append(list(r.out_ids))
        return eng, outs

    on, outs_on = run(256)
    off, outs_off = run(0)
    assert on.chains is not None and off.chains is None
    assert outs_on == outs_off, "heat plane changed engine outputs"
    for k in ("prefix_hits", "prefix_misses", "prefix_evictions",
              "prefix_tokens_saved"):
        assert on.stats[k] == off.stats[k], k
    assert off.chain_stats_report() == {}
    _assert_table_matches_stats(on)


def test_prefix_export_import_chain_attribution():
    """Cross-replica path: exporter counts exported_pages, importer
    counts imported_pages + registers under the learned chain, and the
    imported pages' later evictions attribute to that chain."""
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    src = PagedInferenceEngine(_cfg(num_pages=64), rng_seed=0)
    dst = PagedInferenceEngine(_cfg(num_pages=64), rng_seed=0)
    dst.params = src.params
    ids = _prompt(64, seed=11)
    r = src.submit(ids, sp)
    _drain(src, [r])
    hashes = src.hash_prompt(ids)
    payload = src.export_prefix(hashes)
    assert payload is not None
    n = dst.import_prefix(payload)
    assert n == len(payload["page_hashes"]) > 0
    assert src.stats["prefix_exported_pages"] == len(
        payload["page_hashes"])
    _assert_table_matches_stats(src)
    _assert_table_matches_stats(dst)
    assert dst.chains.totals()["imported_pages"] == n
    # the importer's chain shows the pages as resident
    rows = dst.chains.top(2)
    assert rows[0]["imported_pages"] == n
    assert rows[0]["resident_pages"] == n


# ------------------------------------------------------------------ #
# satellite: metrics_summary()["prefix_cache"] vs pool_stats() parity
# ------------------------------------------------------------------ #

def test_metrics_summary_pool_stats_parity():
    """Drift fix: both surfaces derive from engine.prefix_accounting().
    After a mixed warm/evict workload + telemetry flush, the DELTAS in
    the merged metric store equal the engine's accounting exactly
    (deltas, because the process-global registry accumulates across
    tests in this session)."""
    from ray_tpu.serve.metrics import metrics_summary
    from ray_tpu.llm import telemetry

    def snap():
        out = metrics_summary().get("prefix_cache") or {}
        return {k: out.get(k, 0.0) for k in
                ("hits", "misses", "evictions", "tokens_saved")}

    before = snap()
    eng = PagedInferenceEngine(_cfg(num_pages=24))
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    shared = _prompt(64, seed=21)
    for i in range(8):
        r = eng.submit(shared + _prompt(48, seed=700 + i), sp)
        _drain(eng, [r])
    telemetry.on_step(eng)          # ship the final stat deltas
    after = snap()
    acct = eng.prefix_accounting()
    assert eng.stats["prefix_evictions"] > 0
    for key in ("hits", "misses", "evictions", "tokens_saved"):
        assert int(after[key] - before[key]) == acct[key], key
    # cached_pages gauge (last-write-wins for this proc) == accounting
    pages = metrics_summary()["prefix_cache"]["cached_pages"]
    assert pages.get("paged") == acct["cached_pages"] \
        == eng.pool_stats()["cached_pages"]


def test_chain_gauges_ship_bounded_series():
    """Telemetry ships rtpu_llm_prefix_chain_* for at most top_k chains
    plus the overflow row, labeled with the table's minted identities —
    never raw per-request values."""
    from ray_tpu.llm import telemetry
    from ray_tpu.util.metrics import collect_store

    def chain_keys():
        rec = collect_store().get("rtpu_llm_prefix_chain_hits")
        return set((rec or {}).get("series", ()))

    before = chain_keys()           # other engines in this process may
    eng = PagedInferenceEngine(     # have shipped already
        _cfg(num_pages=24, chain_stats_slots=3, chain_stats_top_k=2))
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    for i in range(10):
        r = eng.submit(_prompt(64, seed=300 + i)
                       + _prompt(16, seed=i), sp)
        _drain(eng, [r])
    eng._chain_ship_t = 0.0         # defeat the publish rate limit
    telemetry.on_step(eng)
    new = chain_keys() - before
    assert new, "chain gauges never shipped"
    labels = {dict(k).get("chain") for k in new}
    allowed = set(eng.chains.labels[:eng.chains._next]) | {OVERFLOW_LABEL}
    assert labels <= allowed
    # bounded: top_k + overflow, independent of distinct prompt count
    assert len(new) <= eng.cfg.chain_stats_top_k + 1
    assert collect_store().get("rtpu_llm_prefix_chain_tracked")


# ------------------------------------------------------------------ #
# directory heat entries: publish shape + worker-death staleness
# ------------------------------------------------------------------ #

def test_directory_heat_entries_unit():
    from ray_tpu.core.directory import DirectoryService
    d = DirectoryService(max_entries=64)
    pages = {bytes([i]) * 16: "handle" for i in range(4)}
    heat = {"model": "tiny", "proc": "h:1", "hit_rate": 0.5,
            "chains": []}
    d.merge("serve:prefix:tiny", put={**pages, "heat:h:1": heat},
            owner="w1")
    # prefix read returns ONLY the heat summaries, not the page keys
    got = d.lookup_prefix("serve:prefix:tiny", "heat:")
    assert got == {"heat:h:1": heat}
    # keyed page queries never see the string-keyed summary
    q = d.lookup("serve:prefix:tiny", keys=list(pages))
    assert set(q["entries"]) == set(pages)
    # a dead replica's heat entry sweeps with its page entries
    assert d.sweep_owner("w1") == 5
    assert d.lookup_prefix("serve:prefix:tiny", "heat:") == {}
    assert d.lookup("serve:prefix:tiny")["entries"] == {}


def test_heat_publish_and_cache_report_cluster(ray_start_regular):
    """Live head: a replica-side PrefixDirectoryClient publishes page
    hashes + its heat summary on one dir_update cadence; the head's
    cache_report folds it; cli cache renders it."""
    from ray_tpu.cli import _cache_frame
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.llm import telemetry
    from ray_tpu.serve.frontdoor.prefix import PrefixDirectoryClient
    from ray_tpu import state as state_mod

    eng = PagedInferenceEngine(_cfg(num_pages=48))
    eng.cache.log.track = True
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    shared = _prompt(64, seed=31)
    for i in range(4):
        r = eng.submit(shared + _prompt(16, seed=400 + i), sp)
        _drain(eng, [r])
    eng._chain_ship_t = 0.0         # defeat the chain publish rate limit
    telemetry.on_step(eng)          # fleet totals via the merged store
    from ray_tpu.util.metrics import collect_store
    collect_store()                 # force the ~2s flusher: the gauges
                                    # must be IN the head store before
                                    # cache_report() folds it

    client = PrefixDirectoryClient("tiny-heat")

    class _Handle:
        _actor_id = b"self"
    client.set_replica_handle(_Handle())
    client._last_publish = -1e9     # defeat the publish rate limit
    assert client.maybe_publish(eng) > 0

    rt = rt_mod.get_runtime_if_exists()
    heats = rt.dirs.lookup_prefix("serve:prefix:tiny-heat", "heat:")
    assert len(heats) == 1
    val = next(iter(heats.values()))
    assert val["model"] == "tiny-heat"
    assert val["pool"]["total_pages"] == 48
    assert val["pool"]["reclaimable_bytes"] == \
        val["pool"]["cached_pages"] * val["pool"]["page_bytes"]
    assert val["chains"][0]["hits"] == eng.stats["prefix_hits"]

    # a second publish with no page deltas still refreshes the summary
    client._last_publish = -1e9
    before_ts = val["ts"]
    client.maybe_publish(eng)
    heats2 = rt.dirs.lookup_prefix("serve:prefix:tiny-heat", "heat:")
    assert next(iter(heats2.values()))["ts"] >= before_ts

    # top_k generous: earlier tests in this process may have shipped
    # their own chain series into the same store
    rep = state_mod.cache_report(top_k=64)
    assert rep["totals"]["hits"] >= eng.stats["prefix_hits"]
    assert any(r["model"] == "tiny-heat" for r in rep["replicas"])
    assert rep["pages"]["total"] >= 48
    assert rep["tenants"], "per-tenant warmth missing"
    hot = eng.chains.top(1)[0]["chain"]
    assert any(c["chain"] == hot for c in rep["chains"])

    frame = _cache_frame(rep)
    assert "prefix cache: hit rate" in frame
    assert hot in frame
    assert "reclaimable" in frame

    # head death of the publisher: owner sweep drops heat + page entries
    swept = rt.dirs.sweep_owner("head")
    assert swept > 0
    assert rt.dirs.lookup_prefix("serve:prefix:tiny-heat", "heat:") == {}
    rep2 = rt.cache_report()
    assert not any(r.get("model") == "tiny-heat"
                   for r in rep2["replicas"])


def test_cache_frame_renders_empty_report():
    """cli cache must render a useful frame on a cold cluster."""
    from ray_tpu.cli import _cache_frame
    frame = _cache_frame({"totals": {"hit_rate": 0.0, "hits": 0,
                                     "misses": 0, "evictions": 0,
                                     "tokens_saved": 0},
                          "chains": [], "replicas": [], "pages": {},
                          "tenants": {}})
    assert "no per-chain series yet" in frame


# ------------------------------------------------------------------ #
# flight events ride the existing ring
# ------------------------------------------------------------------ #

def test_flight_records_prefix_churn():
    import ray_tpu.core.flight as fl
    old = (fl._rec, fl._resolved, fl.evt)
    rec = fl.install_for_test(256)
    try:
        eng = PagedInferenceEngine(_cfg(num_pages=24))
        sp = SamplingParams(max_tokens=2, temperature=0.0)
        for i in range(8):
            r = eng.submit(_prompt(64, seed=600 + i), sp)
            _drain(eng, [r])
        assert eng.stats["prefix_evictions"] > 0
        events = fl.decode(rec.snapshot()["buf"])
        names = [fl.CODES[e[1]][0] for e in events if e[1] in fl.CODES]
        assert "prefix_evict" in names
    finally:
        fl._rec, fl._resolved, fl.evt = old
