"""Fixed-memory per-chain prefix-cache heat table (the engine half of
the cache heat plane).

A *chain* is a family of prompts sharing the same first full KV page —
the chain-head hash ``h_0 = H(salt || page_0_tokens)`` of the engine's
chained content hashes (kv_cache.hash_chain). Every request whose
prompt opens with the same system prompt (under the same tenant salt)
lands in one chain, so chain granularity is exactly the granularity
cache policy cares about: "this assistant's system prompt is hot",
"that tenant's adapter preamble went cold an hour ago".

Memory model — the same discipline as obs/tsdb.py's series table:

- every counter lives in a numpy array preallocated at construction;
  updates are ``arr[slot] += n`` — O(1), no per-update objects;
- distinct chains are capped at ``slots``; the first sight of a chain
  past the cap folds it into slot 0, the ``__overflow__`` sink, so
  client-controlled prompt diversity can NEVER grow engine memory
  (chains already established keep exact per-chain counts);
- per-slot identity (key bytes, display label, tenant label) is
  allocated once at slot creation — bounded by the cap — and reused
  verbatim as the metric label value afterwards, which is what keeps
  the shipped ``rtpu_llm_prefix_chain_*`` series inside the bounded
  top-K/``__overflow__`` vocabulary graftlint GL011 demands;
- ``stats()`` reports the byte ceiling the table can ever reach.

The table is observation only. Nothing in the engine's admission or
eviction policy reads it — the paged engine's outputs are bit-identical
with the table enabled or disabled (tests/test_cache_heat.py pins it).
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

#: slot 0 — where chains past the cap (and pages whose chain was never
#: learned) aggregate. Mirrors obs/tsdb.py's OVERFLOW_KEY sink.
OVERFLOW_LABEL = "__overflow__"

#: per-slot bookkeeping estimate outside the numpy arrays: key dict
#: entry (~64B) + 16B digest + label/tenant strings (~80B). Used only
#: for the stats() byte ceiling — a reporting bound, not an allocator.
_SLOT_OVERHEAD_BYTES = 160


class ChainStatsTable:
    """Per-chain hit/miss/eviction/import/export accounting with a hard
    cardinality cap. NOT thread-safe by itself: updates happen under the
    engine's existing pool lock / stepping serialization (the same call
    sites that mutate ``engine.stats``); report paths read monotonically
    growing arrays, which is safe for telemetry snapshots."""

    def __init__(self, slots: int, page_bytes: int = 0):
        n = int(slots) + 1              # + the __overflow__ sink at 0
        self.cap = int(slots)
        self.page_bytes = int(page_bytes)
        self.hits = np.zeros((n,), np.int64)
        self.misses = np.zeros((n,), np.int64)
        self.tokens_saved = np.zeros((n,), np.int64)
        self.evictions = np.zeros((n,), np.int64)
        self.imported_pages = np.zeros((n,), np.int64)
        self.exported_pages = np.zeros((n,), np.int64)
        self.resident_pages = np.zeros((n,), np.int64)
        # spill tier (llm/tiering.py): pages of the chain resident in
        # the host tier, and pages promoted back into HBM from it —
        # zero everywhere while kv_spill is off, so legacy accounting
        # is reproduced exactly
        self.spilled_pages = np.zeros((n,), np.int64)
        self.promotions = np.zeros((n,), np.int64)
        self.last_hit = np.zeros((n,), np.float64)  # time.monotonic()
        self._slot_by_key: dict[bytes, int] = {}
        # slot identity, written once at creation (bounded label mint)
        self.labels: list[str] = [OVERFLOW_LABEL] + [""] * self.cap
        self.tenants: list[str] = [OVERFLOW_LABEL] + [""] * self.cap
        self._next = 1
        self.overflow_assignments = 0   # slot_for calls folded into 0

    # -- slot assignment (allocates at most `cap` times, ever) ---------

    def slot_for(self, head: bytes, salt: bytes = b"") -> int:
        """Slot for the chain-head hash; assigns a fresh slot on first
        sight while capacity remains, else the overflow sink. Steady
        state is one dict lookup."""
        s = self._slot_by_key.get(head)
        if s is not None:
            return s
        if self._next > self.cap:
            self.overflow_assignments += 1
            return 0
        s = self._next
        self._next = s + 1
        self._slot_by_key[head] = s
        self.labels[s] = head.hex()[:12]
        self.tenants[s] = salt.hex()[:8] if salt else "base"
        return s

    def peek(self, head: bytes) -> int:
        """Slot for a chain-head, or the overflow sink — never assigns."""
        return self._slot_by_key.get(head, 0)

    # -- O(1) hot-path updates (mirrors of the engine.stats bumps) -----

    def hit(self, slot: int, pages: int, tokens: int = 0) -> None:
        self.hits[slot] += pages
        self.tokens_saved[slot] += tokens
        self.last_hit[slot] = time.monotonic()

    def miss(self, slot: int, pages: int) -> None:
        self.misses[slot] += pages

    def evict(self, slot: int) -> None:
        self.evictions[slot] += 1

    def imported(self, slot: int, pages: int) -> None:
        self.imported_pages[slot] += pages

    def exported(self, slot: int, pages: int) -> None:
        self.exported_pages[slot] += pages

    def resident_add(self, slot: int) -> None:
        self.resident_pages[slot] += 1

    def resident_sub(self, slot: int) -> None:
        self.resident_pages[slot] -= 1

    def spilled_add(self, slot: int) -> None:
        self.spilled_pages[slot] += 1

    def spilled_sub(self, slot: int) -> None:
        self.spilled_pages[slot] -= 1

    def promoted(self, slot: int, pages: int) -> None:
        self.promotions[slot] += pages

    # -- reporting -----------------------------------------------------

    def _row(self, s: int, now: float) -> dict:
        return {
            "chain": self.labels[s],
            "tenant": self.tenants[s],
            "hits": int(self.hits[s]),
            "misses": int(self.misses[s]),
            "tokens_saved": int(self.tokens_saved[s]),
            "evictions": int(self.evictions[s]),
            "imported_pages": int(self.imported_pages[s]),
            "exported_pages": int(self.exported_pages[s]),
            "resident_pages": int(self.resident_pages[s]),
            "resident_bytes": int(self.resident_pages[s]) * self.page_bytes,
            "spilled_pages": int(self.spilled_pages[s]),
            "promotions": int(self.promotions[s]),
            "last_hit_age_s": round(now - self.last_hit[s], 3)
            if self.last_hit[s] else None,
        }

    def top(self, k: int, now: Optional[float] = None) -> list[dict]:
        """The k hottest tracked chains (by hits, ties to recency) plus
        the overflow sink whenever it holds anything — the bounded set
        telemetry ships and the directory publishes."""
        now = time.monotonic() if now is None else now
        used = self._next
        order = sorted(range(1, used),
                       key=lambda s: (-int(self.hits[s]),
                                      -self.last_hit[s]))
        rows = [self._row(s, now) for s in order[:max(int(k), 0)]]
        if (self.hits[0] or self.misses[0] or self.evictions[0]
                or self.overflow_assignments):
            rows.append(self._row(0, now))
        return rows

    def totals(self) -> dict:
        """Whole-table sums (overflow included). The counter-verification
        contract: each total equals the matching engine.stats aggregate —
        every aggregate bump has exactly one chain attribution."""
        return {
            "hits": int(self.hits.sum()),
            "misses": int(self.misses.sum()),
            "tokens_saved": int(self.tokens_saved.sum()),
            "evictions": int(self.evictions.sum()),
            "imported_pages": int(self.imported_pages.sum()),
            "exported_pages": int(self.exported_pages.sum()),
            "resident_pages": int(self.resident_pages.sum()),
            "spilled_pages": int(self.spilled_pages.sum()),
            "promotions": int(self.promotions.sum()),
        }

    def stats(self) -> dict:
        arrays = (self.hits, self.misses, self.tokens_saved,
                  self.evictions, self.imported_pages,
                  self.exported_pages, self.resident_pages,
                  self.spilled_pages, self.promotions, self.last_hit)
        return {
            "slots": self.cap,
            "tracked": self._next - 1,
            "overflow_assignments": self.overflow_assignments,
            "page_bytes": self.page_bytes,
            # the ceiling: preallocated arrays + at most `cap` slot
            # identities — what "client prompts can never grow engine
            # memory" means in bytes
            "max_bytes": sum(a.nbytes for a in arrays)
            + self.cap * _SLOT_OVERHEAD_BYTES,
        }

    def report(self, top_k: int = 8) -> dict:
        now = time.monotonic()
        return {"table": self.stats(), "totals": self.totals(),
                "chains": self.top(top_k, now)}
