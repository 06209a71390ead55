"""Tiered KV-cache (llm/tiering.py + the engine/cluster surfaces):
policy/tier unit mechanics, demote→promote bitwise round-trips, tier
on/off output bit-equality with legacy accounting preserved, budget
expiry under pressure, proactive re-warm, cross-replica promote of a
prefix NO replica holds hot (spill: directory entries + the object
store), stale-entry counted drops with cold-prefill correctness, and
store drain on teardown."""
import threading
import time

import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.llm.tiering import SpillPolicy, SpillTier
from ray_tpu.models import llama, mla_moe

TINY = llama.llama_tiny(vocab_size=258, max_seq_len=640)
# ONE latent pool a layer where TINY has a k and a v pool
LATENT = mla_moe.mla_moe_tiny(vocab_size=258, max_seq_len=640)


def _cfg(**kw):
    defaults = dict(model=TINY, max_batch_size=4, page_size=8,
                    num_pages=32, max_pages_per_seq=16, chunk_size=16,
                    enable_prefix_caching=True)
    defaults.update(kw)
    return PagedEngineConfig(**defaults)


def _prompt(n, seed=0):
    return list(np.random.RandomState(seed).randint(1, 250, (n,)))


def _run_one(eng, ids, max_tokens=4):
    r = eng.submit(ids, SamplingParams(max_tokens=max_tokens,
                                       temperature=0.0))
    while not r.done:
        eng.step()
    return list(r.out_ids)


def _flush(eng, count=8, seed0=9000, n=96):
    """Push `count` distinct prompts through so every refcount-0 page
    of earlier chains falls off the LRU — the demote site."""
    for i in range(count):
        _run_one(eng, _prompt(n, seed=seed0 + i), max_tokens=2)


def _assert_spill_parity(eng):
    """The tier's counter-verification contract: chain-table sums ==
    engine.stats aggregates == live tier residence, and
    prefix_accounting() (THE single accounting source) agrees."""
    t = eng.chains.totals()
    resident = eng.spill.resident_pages() if eng.spill else 0
    assert t["spilled_pages"] == resident
    assert t["promotions"] == eng.stats["spill_promotions"]
    acct = eng.prefix_accounting()
    assert acct["spill_resident_pages"] == resident
    if eng.spill is not None:
        assert acct["spill_resident_bytes"] == eng.spill.resident_bytes
        assert acct["spill_demotions"] == eng.stats["spill_demotions"]


# ------------------------------------------------------------------ #
# config + policy/tier units
# ------------------------------------------------------------------ #

def test_kv_spill_config_validation():
    with pytest.raises(ValueError):
        _cfg(kv_spill=True, enable_prefix_caching=False)
    with pytest.raises(ValueError):
        _cfg(kv_spill=True, kv_spill_max_bytes=0)


def test_spill_policy_gates_unit():
    from ray_tpu.llm.chainstats import ChainStatsTable
    t = ChainStatsTable(slots=4, page_bytes=10)
    s = t.slot_for(b"a" * 16)
    now = time.monotonic()
    pol = SpillPolicy(min_hits=2)
    assert not pol.admit(t, s, now)
    t.hit(s, pages=2)
    assert pol.admit(t, s, now)
    pol2 = SpillPolicy(max_idle_s=1.0)
    t.last_hit[s] = now - 5.0
    assert not pol2.admit(t, s, now)
    t.last_hit[s] = now - 0.5
    assert pol2.admit(t, s, now)
    # no table / never-learned chain: no signal, admit (budget governs)
    assert SpillPolicy(min_hits=99).admit(None, s, now)
    assert SpillPolicy(min_hits=99).admit(t, 0, now)
    # re-warm: hottest spilled slot, only with pool headroom
    s2 = t.slot_for(b"b" * 16)
    t.hit(s2, pages=5)
    pol3 = SpillPolicy(rewarm_min_hits=1, rewarm_free_frac=0.5)
    assert pol3.rewarm_slot(t, {s, s2}, 0.9) == s2
    assert pol3.rewarm_slot(t, {s, s2}, 0.1) is None
    assert pol3.rewarm_slot(None, {s, s2}, 0.9) is None


def test_spill_tier_budget_unit():
    pools = [{"ckv": np.zeros((2, 2), np.float32)}]
    tier = SpillTier(max_bytes=30, page_nbytes=10)
    hs = [bytes([i]) * 16 for i in range(4)]
    expired = [tier.add(h, 0, pools, now=float(i))
               for i, h in enumerate(hs)]
    # the 4th add pushed the tier over budget: FIFO victim (no chain
    # table bound) is the oldest entry
    assert expired[:3] == [[], [], []]
    assert expired[3] == [(hs[0], 0)]
    assert tier.resident_pages() == 3
    assert tier.resident_bytes == 30
    # publish delta nets the expired entry out of `new`
    new, gone = tier.drain_publish_delta()
    assert set(new) == set(hs[1:])
    assert gone == [hs[0]]
    assert tier.drain_publish_delta() == ((), ())
    # requeue puts still-resident hashes back for the next drain
    tier.requeue_publish([hs[1], hs[0]])
    new, _ = tier.drain_publish_delta()
    assert new == [hs[1]]
    # covered_run / chain_of / touch
    assert tier.covered_run(hs[1:]) == 3
    assert tier.covered_run(hs) == 0
    assert tier.chain_of(hs[1]) == 0
    # a page larger than the whole budget is refused outright
    t2 = SpillTier(max_bytes=5, page_nbytes=10)
    assert t2.add(b"h" * 16, 1, pools) == [(b"h" * 16, 1)]
    assert t2.resident_pages() == 0
    # teardown drops everything and reports it
    assert sorted(h for h, _c in tier.clear()) == sorted(hs[1:])
    assert tier.resident_pages() == 0 and tier.resident_bytes == 0


# ------------------------------------------------------------------ #
# engine integration: demote/promote, bit-equality, budget, re-warm
# ------------------------------------------------------------------ #

def test_tier_on_off_bit_identical_outputs():
    """The iron invariant, engine-local: identical greedy outputs with
    the tier on vs off across an evict-then-revisit workload, and with
    kv_spill off every spill counter stays exactly zero (legacy
    accounting reproduced)."""
    shared = _prompt(96, seed=3)

    def run(spill):
        kw = {"kv_spill": True} if spill else {}
        eng = PagedInferenceEngine(_cfg(**kw), rng_seed=0)
        outs = [_run_one(eng, shared + _prompt(16, seed=50), 8)]
        _flush(eng, seed0=9100)
        outs.append(_run_one(eng, shared + _prompt(16, seed=51), 8))
        return eng, outs

    on, outs_on = run(True)
    off, outs_off = run(False)
    assert outs_on == outs_off, "spill tier changed engine outputs"
    assert on.stats["spill_demotions"] > 0
    assert on.stats["spill_promotions"] > 0
    for k in ("spill_pages", "spill_bytes", "spill_demotions",
              "spill_promotions", "spill_expired", "spill_drops"):
        assert off.stats[k] == 0, k
    assert off.spill is None
    _assert_spill_parity(on)
    _assert_spill_parity(off)


@pytest.mark.parametrize("model", [TINY, LATENT], ids=["llama", "latent"])
def test_demote_promote_bitwise_roundtrip(model):
    """A promoted page is bit-identical to a never-evicted one: export
    the hot prefix, evict everything, promote it back via a resubmit,
    export again — payloads match bitwise, every pool of every layer."""
    eng = PagedInferenceEngine(_cfg(kv_spill=True, model=model), rng_seed=0)
    ids = _prompt(96, seed=11)
    _run_one(eng, ids, 2)
    hashes = eng.hash_prompt(ids)
    before = eng.export_prefix(hashes)
    assert before is not None and len(before["page_hashes"]) > 0
    _flush(eng, seed0=9200)
    assert eng.cached_prefix_len(hashes) == 0   # fully evicted
    assert eng.spill.covered_run(hashes) == len(hashes)
    _run_one(eng, ids, 2)                       # admission promote
    assert eng.stats["spill_promotions"] >= len(hashes)
    after = eng.export_prefix(hashes)
    assert after["page_hashes"] == before["page_hashes"]
    assert len(after["pages"]) == model.n_layers
    for la, lb in zip(after["pages"], before["pages"]):
        assert set(la) == set(lb) == set(eng.caches[0])
        for name in la:
            assert np.array_equal(la[name], lb[name])
    _assert_spill_parity(eng)


def test_spill_budget_eviction_under_pressure():
    """Tier bytes never exceed kv_spill_max_bytes under sustained
    eviction pressure; overflow expires coldest-first and is counted;
    live requests are never touched (outputs stay correct)."""
    probe = PagedInferenceEngine(_cfg(kv_spill=True), rng_seed=0)
    pnb = probe.spill.page_nbytes
    budget = 4 * pnb
    eng = PagedInferenceEngine(
        _cfg(kv_spill=True, kv_spill_max_bytes=budget), rng_seed=0)
    out = _run_one(eng, _prompt(96, seed=23), 8)
    _flush(eng, count=10, seed0=9300)
    assert eng.spill.resident_bytes <= budget
    assert eng.spill.resident_pages() <= 4
    assert eng.stats["spill_expired"] > 0
    assert eng.stats["spill_pages"] > 4     # captured far more than kept
    _assert_spill_parity(eng)
    # correctness under pressure: same prompt on a fresh engine agrees
    cold = PagedInferenceEngine(_cfg(), rng_seed=0)
    cold.params = eng.params
    assert _run_one(cold, _prompt(96, seed=23), 8) == out


def test_maybe_rewarm_promotes_hot_chain():
    """Proactive re-warm: the hottest spilled chain comes back into
    idle pool headroom without any request asking for it."""
    eng = PagedInferenceEngine(_cfg(kv_spill=True), rng_seed=0)
    shared = _prompt(96, seed=7)
    for i in range(3):                      # make the chain hot
        _run_one(eng, shared + _prompt(16, seed=100 + i), 2)
    _flush(eng, seed0=9400)
    hashes = eng.hash_prompt(shared)
    assert eng.cached_prefix_len(hashes) == 0
    # the flushed pool has little FREE headroom (pages sit cached);
    # drop the gate so the test exercises the promote, not the gate
    eng.spill.policy.rewarm_free_frac = 0.0
    n = eng.maybe_rewarm()
    assert n > 0
    assert eng.cached_prefix_len(hashes) > 0
    assert eng.stats["spill_promotions"] == n
    _assert_spill_parity(eng)
    # rewarm is idempotent once the run is hot
    assert eng.maybe_rewarm() == 0


def test_spill_teardown_engine_only():
    """spill_teardown drops every entry with exact accounting — the
    engine-only half of the store-drain guarantee."""
    eng = PagedInferenceEngine(_cfg(kv_spill=True), rng_seed=0)
    _run_one(eng, _prompt(96, seed=29), 2)
    _flush(eng, count=4, seed0=9500)
    assert eng.spill.resident_pages() > 0
    dropped = eng.spill_teardown()
    assert dropped > 0
    assert eng.spill.resident_pages() == 0
    assert eng.spill.resident_bytes == 0
    assert eng.stats["spill_expired"] >= dropped
    _assert_spill_parity(eng)


# ------------------------------------------------------------------ #
# telemetry + metrics_summary fold
# ------------------------------------------------------------------ #

def test_metrics_summary_spill_fold():
    """Counter-verification through the whole metrics plane: the
    rtpu_llm_prefix_spill_* deltas in the merged store equal the
    engine's prefix_accounting(), and metrics_summary()["cache"]
    carries the spill fold."""
    from ray_tpu.llm import telemetry
    from ray_tpu.serve.metrics import metrics_summary

    def snap():
        out = (metrics_summary().get("cache") or {}).get("spill") or {}
        return {k: out.get(k, 0.0) for k in
                ("demotions", "promotions", "expired", "drops",
                 "spilled_pages", "spilled_bytes")}

    before = snap()
    eng = PagedInferenceEngine(_cfg(kv_spill=True), rng_seed=0)
    shared = _prompt(96, seed=37)
    _run_one(eng, shared, 2)
    _flush(eng, seed0=9600)
    _run_one(eng, shared, 2)        # promote
    telemetry.on_step(eng)          # ship the final stat deltas
    after = snap()
    acct = eng.prefix_accounting()
    assert acct["spill_demotions"] > 0 and acct["spill_promotions"] > 0
    for summary_key, acct_key in (
            ("demotions", "spill_demotions"),
            ("promotions", "spill_promotions"),
            ("expired", "spill_expired"),
            ("drops", "spill_drops"),
            ("spilled_pages", "spill_pages"),
            ("spilled_bytes", "spill_bytes")):
        assert int(after[summary_key] - before[summary_key]) \
            == acct[acct_key], summary_key
    # residence gauges (last-write-wins for this proc's engine tag)
    spill = metrics_summary()["cache"]["spill"]
    assert spill["resident_pages"] == acct["spill_resident_pages"]
    assert spill["resident_bytes"] == acct["spill_resident_bytes"]


# ------------------------------------------------------------------ #
# cluster: spill: directory entries + store promote + teardown drain
# ------------------------------------------------------------------ #

class _Handle:
    def __init__(self, actor_id=b"self"):
        self._actor_id = actor_id


def test_cross_replica_promote_from_store(ray_start_regular):
    """The tentpole end-to-end: replica A demotes a prefix out of
    device memory entirely, publishes spill: entries backed by the
    object store; replica B — which never saw the prompt — imports it
    straight from the store and decodes bit-identically to a cold
    prefill."""
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.serve.frontdoor.prefix import PrefixDirectoryClient

    src = PagedInferenceEngine(_cfg(kv_spill=True), rng_seed=0)
    src.cache.log.track = True
    dst = PagedInferenceEngine(_cfg(num_pages=64), rng_seed=0)
    dst.params = src.params
    shared = _prompt(96, seed=13)
    _run_one(src, shared, 2)
    hashes = src.hash_prompt(shared)
    assert hashes
    _flush(src, seed0=9700)
    assert src.cached_prefix_len(hashes) == 0   # NO replica holds it hot
    assert src.spill.covered_run(hashes) == len(hashes)

    ca = PrefixDirectoryClient("tiny-tier")
    ca.set_replica_handle(_Handle(b"replica-a"))
    ca._last_publish = -1e9
    ca.maybe_publish(src)

    rt = rt_mod.get_runtime_if_exists()
    spills = rt.dirs.lookup_prefix("serve:prefix:tiny-tier", "spill:")
    assert set("spill:" + h.hex() for h in hashes) <= set(spills)
    val = next(iter(spills.values()))
    assert val["m"] == "tiny-tier" and isinstance(val["oid"], bytes)
    # staged→stored flip happened: host copies freed, segments pinned
    assert src.spill.stats()["staged_pages"] == 0
    assert src.spill.stats()["stored_segments"] > 0

    cb = PrefixDirectoryClient("tiny-tier")
    cb.set_replica_handle(_Handle(b"replica-b"))
    n = cb.maybe_import(dst, threading.Lock(), shared)
    assert n == len(hashes)
    assert dst.stats["spill_promotions"] == n
    assert dst.cached_prefix_len(hashes) == len(hashes)
    out_b = _run_one(dst, shared + _prompt(16, seed=500), 8)
    cold = PagedInferenceEngine(_cfg(num_pages=64), rng_seed=0)
    cold.params = src.params
    assert _run_one(cold, shared + _prompt(16, seed=500), 8) == out_b
    # the warm arm actually used the promoted pages
    assert dst.stats["prefix_hits"] >= n


def test_stale_spill_entry_counted_drop_and_cold_prefill(
        ray_start_regular):
    """Iron invariant at the cluster layer: spill: entries pointing at
    a garbage store payload cost a counted drop + cold prefill, never
    a wrong answer — and the stale keys leave the directory."""
    import ray_tpu
    from ray_tpu.core import directory as cdir
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.serve.frontdoor.prefix import PrefixDirectoryClient

    eng = PagedInferenceEngine(_cfg(), rng_seed=0)
    shared = _prompt(96, seed=17)
    hashes = eng.hash_prompt(shared)
    bad_ref = ray_tpu.put(
        {"page_size": 8, "page_hashes": [], "pages": []})
    cdir.update("serve:prefix:tiny-stale", put={
        "spill:" + h.hex(): {"m": "tiny-stale", "oid": bad_ref.binary()}
        for h in hashes})

    cb = PrefixDirectoryClient("tiny-stale")
    cb.set_replica_handle(_Handle(b"replica-b"))
    n = cb.maybe_import(eng, threading.Lock(), shared)
    assert n == 0
    assert eng.stats["spill_drops"] == len(hashes)
    rt = rt_mod.get_runtime_if_exists()
    assert rt.dirs.lookup_prefix(
        "serve:prefix:tiny-stale", "spill:") == {}
    # the request itself: plain cold prefill, correct bytes
    out = _run_one(eng, shared, 8)
    cold = PagedInferenceEngine(_cfg(), rng_seed=0)
    cold.params = eng.params
    assert _run_one(cold, shared, 8) == out


def test_spill_teardown_drains_store(ray_start_regular):
    """Materialized segments are refcounted store objects pinned ONLY
    by the tier: teardown drops the refs and the store settles back to
    its pre-spill baseline, and the next publish cadence retracts the
    spill: directory entries."""
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.serve.frontdoor.prefix import PrefixDirectoryClient

    rt = rt_mod.get_runtime_if_exists()
    eng = PagedInferenceEngine(_cfg(kv_spill=True), rng_seed=0)
    eng.cache.log.track = True
    base = rt.store.bytes_in_use()
    _run_one(eng, _prompt(96, seed=41), 2)
    _flush(eng, count=4, seed0=9800)
    ca = PrefixDirectoryClient("tiny-drain")
    ca.set_replica_handle(_Handle(b"replica-a"))
    ca._last_publish = -1e9
    ca.maybe_publish(eng)
    assert rt.store.bytes_in_use() > base
    assert rt.dirs.lookup_prefix("serve:prefix:tiny-drain", "spill:")

    assert eng.spill_teardown() > 0
    deadline = time.monotonic() + 5.0
    while rt.store.bytes_in_use() > base and \
            time.monotonic() < deadline:
        time.sleep(0.05)            # ref drops land asynchronously
    assert rt.store.bytes_in_use() == base
    # the retraction rides the normal publish cadence
    ca._last_publish = -1e9
    ca.maybe_publish(eng)
    assert rt.dirs.lookup_prefix(
        "serve:prefix:tiny-drain", "spill:") == {}
