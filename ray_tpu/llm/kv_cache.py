"""The paged KV cache's host side: which sequence holds which page.

Every decision about pages is made here; ``PagedInferenceEngine``
(llm/paged_engine.py) keeps the step, the launches, the bookings and the
programs, and asks ONE ``KVCache`` for pages whatever kinds of layer the
model has. From the bottom:

  PageSpace    one page-id space: free list, references, the content index
               of published pages, the tiers of published pages nobody
               holds, and whoever hears of a publish or an eviction.
  IndexLog     what hears of the FULL pages' index: chain heat, the
               cluster directory's delta, the spill tier's demotion.
  FullPages,   a cache KIND, how one kind of layer holds a sequence:
  WindowPages, every page while it lives (a flat table, THE prefix index),
  StateSlots   a ring of what one window and the dispatches in flight need
               (the pages behind handed back), or a fixed-size recurrent
               state in the sequence's decode slot, with a pool of hashed
               snapshots of it that a cached prefix is resumed from.
  KVCache      the kinds a model has (``model.cache_layers``: one kind a
               layer), behind the engine's vocabulary; a kind says in
               admit / reuse what of a cached prefix it can back.

Full pages are content-addressed by a chained hash h_i = H(h_{i-1} ||
page_token_ids): the flat dict is an implicit trie. ``refs`` counts the
requests that hold a page; at zero a published page parks in a tier and is
reclaimed when allocation outruns the free list. No lock here: the cache
is touched on the stepping thread and, from other threads, under
``engine._lock``. No counters either: it books into the engine's ``stats``.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..core import flight
from .engine import _Request

# What a model with sliding-window layers adds to engine.stats: pages in
# and out of each pool, each pool's live pages a decode booking, prefixes
# cut or lost for want of a window tail, the window layers' work (PERF.md §3)
WINDOW_COUNTERS = (
    "window_pages_claimed", "window_pages_returned", "full_pages_claimed",
    "full_pages_returned", "window_pool_live_pages", "full_pool_live_pages",
    "window_evictions", "prefix_tail_cut", "prefix_tail_lost",
    "prefix_tail_tokens_lost", "decode_live_wpages", "decode_table_wpages",
    "prefill_ctx_wpages", "prefill_attn_wpairs")


# What a model with recurrent-state layers adds to engine.stats: snapshots
# filed, resumed, reclaimed (and of those the ones from the middle of a
# prompt / from a prompt's end: state_evictions is their sum) and refused
# (a pool whose every snapshot is pinned), prompt tokens a resumed snapshot
# covered / a request still ran, full pages a hit found beyond the newest
# snapshot (re-run, not trusted), admissions that would have resumed a
# snapshot the pool had reclaimed (its ghost) and the prompt tokens that
# one would have saved them, the snapshots the pool held at each decode
# booking, and the pages of the page pool beside it that some sequence
# held there (PERF.md §3)
STATE_COUNTERS = (
    "state_snapshots_taken", "state_snapshots_refused",
    "state_snapshot_hits", "state_evictions", "state_hit_tokens",
    "state_rerun_tokens", "state_pages_untrusted", "state_pool_live",
    "full_pool_live_pages", "state_ghost_hits", "state_ghost_hit_tokens",
    "state_evictions_mid", "state_evictions_end")


def window_need(cfg, model, prefill_rows: int) -> tuple:
    """(a ring's width, the pages the window pool must hold) under a
    budget of ``prefill_rows`` chunk-rows a prefill dispatch; (0, 0)
    without sliding layers. A ring is a window wide plus the most one
    dispatch writes past a sequence's oldest query (a prefill's rows, or
    a sequence's two decode windows in flight: the engine's step()),
    whatever the context: no page bucket, no program. The pool: a ring a
    sequence, and what two prefills in flight hold unbooked."""
    if not model.cache_window(cfg.model):
        return 0, 0
    write = max(prefill_rows * cfg.chunk_size, 2 * cfg.decode_window,
                cfg.spec_tokens + 1)
    ring = model.window_ring_pages(cfg.model, cfg.page_size, write)
    return ring, (cfg.max_batch_size * ring
                  + 2 * -(-write // cfg.page_size) + 1)


class PageSpace:
    """One page-id space. Page 0 is its write sink (idle slots' dummy
    writes land there, never attended) and is never handed out. ``tiers``
    hold published pages nobody holds, insertion order = eviction order,
    the first non-empty tier reclaimed first (or, where a kind hands the
    space an ``order``, the first non-empty of the tiers that returns);
    ``lru`` is the last. ``heard``: the observers of its index (published
    / forgot); ``evictions``: the counter a reclaim grows."""

    def __init__(self, num_pages: int, stats: dict, evictions: str,
                 tiers: int = 1):
        self.num_pages = num_pages
        self.free = list(range(1, num_pages))
        self.refs = np.zeros((num_pages,), np.int32)
        self.hash_to_page: dict[bytes, int] = {}
        self.page_to_hash: dict[int, bytes] = {}
        self.tiers = [OrderedDict() for _ in range(tiers)]
        self.lru: "OrderedDict[int, None]" = self.tiers[-1]
        self.stats, self.evictions, self.heard = stats, evictions, ()
        self.order = None

    def parked(self) -> int:
        return sum(map(len, self.tiers))

    def avail(self) -> int:
        """Pages allocatable right now: truly free + reclaimable."""
        return len(self.free) + self.parked()

    def live(self) -> int:
        """Pages some request holds."""
        return self.num_pages - 1 - self.avail()

    def take(self, refs: int = 1) -> int:
        """One allocatable page, held ``refs`` times: off the free list,
        else the oldest parked page, forgotten. Callers check avail()."""
        if self.free:
            pid = self.free.pop()
        else:
            tiers = self.tiers if self.order is None else self.order()
            pid, _ = next(t for t in tiers if t).popitem(last=False)
            self.forget(pid)
            self.stats[self.evictions] += 1
        self.refs[pid] = refs
        return pid

    def unpark(self, pid: int) -> None:
        """Take a published page nobody holds out of the tiers."""
        for tier in self.tiers:
            if tier.pop(pid, False) is not False:
                return

    def pin(self, pid: int) -> None:
        """Hold a page for a request; a parked page leaves its tier."""
        if self.refs[pid] == 0:
            self.unpark(pid)
        self.refs[pid] += 1

    def unpin(self, pid: int, cold: bool = False) -> None:
        """Drop one reference; at zero a published page parks (``cold``:
        in the tier reclaimed first), any other is free again."""
        self.refs[pid] -= 1
        if self.refs[pid] > 0:
            return
        if pid in self.page_to_hash:
            (self.tiers[0] if cold else self.lru)[pid] = None
        else:
            self.free.append(pid)

    def publish(self, pid: int, h: bytes, chain: int = -1) -> None:
        if pid in self.page_to_hash or h in self.hash_to_page:
            return      # already published, or duplicate content elsewhere
        self.page_to_hash[pid] = h
        self.hash_to_page[h] = pid
        for o in self.heard:
            o.published(pid, h, chain)

    def forget(self, pid: int) -> None:
        """Drop a reclaimed page's content hash; the observers hear of
        it before the page has its next owner."""
        h = self.page_to_hash.pop(pid, None)
        if h is not None and self.hash_to_page.get(h) == pid:
            del self.hash_to_page[h]
        else:
            h = None
        for o in self.heard:
            o.forgot(pid, h)

    def run(self, hashes) -> list[int]:
        """The pages of the longest head run of a chain the index holds."""
        pids: list[int] = []
        for h in hashes:
            if h not in self.hash_to_page:
                break
            pids.append(self.hash_to_page[h])
        return pids


class IndexLog:
    """What hears of the full pages' index (the window pages' is the
    engine's alone). ``chains``: the per-chain heat table, observation
    only; ``chain_of`` maps a published page to the chain slot it was
    published under, so an eviction needs no hash, and a page whose chain
    was never learned folds to the overflow sink (per-chain evictions sum
    to prefix_evictions). ``new`` / ``dropped``: hashes published /
    forgotten since the last drain, kept while ``track`` is on and touched
    from the stepping thread alone. ``demote``: the spill tier's hook,
    (page, hash, chain slot) of an evicted page, before anyone else."""

    def __init__(self, space: PageSpace):
        self.index, self.cap = space.hash_to_page, 4 * space.num_pages
        self.track = False
        self.new: list[bytes] = []
        self.dropped: list[bytes] = []
        self.chains = self.demote = None
        self.chain_of: dict[int, int] = {}

    def published(self, pid: int, h: bytes, chain: int) -> None:
        if self.chains is not None and chain >= 0:
            self.chain_of[pid] = chain
            self.chains.resident_add(chain)
        if self.track:
            self.new.append(h)
            if len(self.new) > self.cap:
                # publisher not draining: compress to a full resync
                self.new = list(self.index)

    def forgot(self, pid: int, h: Optional[bytes]) -> None:
        if self.demote is not None and h is not None:
            self.demote(pid, h, self.chain_of.get(pid))
        if h is not None and self.track:
            self.dropped.append(h)
            if len(self.dropped) > self.cap:
                # stale entries are hints the importer validates anyway
                del self.dropped[:]
        if self.chains is not None:
            slot = self.chain_of.pop(pid, None)
            if slot is None:
                slot = 0
            else:
                self.chains.resident_sub(slot)
            self.chains.evict(slot)
            flight.evt(flight.PREFIX_EVICT, pid, slot)

    def drain(self) -> tuple:
        """-> (new, dropped) since the last drain, filtered against the
        index so a publish-then-evict (or the reverse) nets out."""
        new, self.new = self.new, []
        dropped, self.dropped = self.dropped, []
        return ([h for h in dict.fromkeys(new) if h in self.index],
                [h for h in dict.fromkeys(dropped) if h not in self.index])


class _Kind:
    """How one kind of layer holds a sequence's keys: a page space, a
    block table a slot, and the request's own list of what it holds
    (``req.pages``, or ``req.wpages`` from logical page ``req.wlo``), which
    only the kind that owns it touches. A kind answers held, ensure,
    publish, release, and the bookings advanced / booked_prefill /
    launched_decode (nothing, unless the kind says otherwise)."""

    name = ""
    bucketed = True     # is its table cut to the dispatch's page bucket?

    def __init__(self, cfg, stats: dict, space: PageSpace, width: int):
        self.page, self.stats, self.space = cfg.page_size, stats, space
        self.table = np.zeros((cfg.max_batch_size, width), np.int32)

    def _count(self, what: str, n: int) -> None:
        """<kind>_<what>: a key only a cache of two kinds has."""
        key = f"{self.name}_{what}"
        if key in self.stats:
            self.stats[key] += n

    def ensure(self, req: _Request, upto_tokens: int) -> bool:
        """Grow req's pages to cover upto_tokens; False if the pool is
        dry (the window pool cannot be while it holds its floor)."""
        have = self.held(req)
        need = -(-upto_tokens // self.page) - have
        if need > 0:
            if self.space.avail() < need:
                return False
            for p in range(have, have + need):
                self._put(req, p, self.space.take())
            self._count("pages_claimed", need)
        return True

    advanced = booked_prefill = launched_decode = lambda self, *_: None

    def booked_decode(self) -> None:
        self._count("pool_live_pages", self.space.live())

    def rows(self, slots, cols: int, prefill) -> np.ndarray:
        """What a program takes as this kind's table: the rows of engine
        slots ``slots``, ``cols`` wide; -1 is a row of zeros (padding, an
        idle row, a warm-up's: its writes route to the sink)."""
        out = np.zeros((len(slots), cols), np.int32)
        for i, slot in enumerate(slots):
            if slot >= 0:
                out[i] = self.table[slot, :cols]
        return out


class FullPages(_Kind):
    """Layers that keep every key: a sequence holds every page while it
    lives, in a flat table [max_batch_size, max_pages_per_seq]; its index
    is THE prefix index (KVCache.match: whole-chunk runs of a chain)."""

    name = "full"

    def __init__(self, cfg, stats: dict):
        super().__init__(cfg, stats, PageSpace(
            cfg.num_pages, stats, "prefix_evictions"), cfg.max_pages_per_seq)
        self.log = IndexLog(self.space)
        self.space.heard = (self.log,)

    def held(self, req: _Request) -> int:
        return len(req.pages)

    def _put(self, req: _Request, p: int, pid: int) -> None:
        req.pages.append(pid)
        self.table[req.slot, p] = pid

    def claim(self, req: _Request, slot: int, matched: list[int],
              n_pages: int) -> bool:
        """Give req slot ``slot`` and n_pages pages: pin `matched` (a
        cached prefix run), then allocate the rest. False — with NO side
        effect — when the pool cannot cover the remainder. Matches are
        pinned BEFORE any allocation (which could evict one); a parked
        match is no eviction candidate, so it counts against availability.
        (Admission and PD import share it: one accounting.)"""
        sp, need = self.space, n_pages - len(matched)
        if need > sp.avail() - sum(1 for p in matched if sp.refs[p] == 0):
            return False
        for pid in matched:
            sp.pin(pid)
        req.slot = slot
        req.pages = list(matched) + [sp.take() for _ in range(need)]
        self.table[slot, :n_pages] = req.pages
        self._count("pages_claimed", n_pages)
        return True

    def publish(self, req: _Request, lo: int, hi: int, hashes) -> None:
        for j in range(lo, hi):
            self.space.publish(req.pages[j], hashes[j], req.chain_slot)

    def release(self, req: _Request) -> None:
        for pid in req.pages:
            self.space.unpin(pid)
        self._count("pages_returned", len(req.pages))
        req.pages = []


class WindowPages(_Kind):
    """Sliding-window layers: a sequence holds the pages of one window and
    of the dispatches in flight, in a ring table (logical page p in
    column p % ring), and hands back the ones behind them (advanced).
    Published pages park in two tiers (cold): a prefix of N tokens is a
    hit only with its TAIL, the pages of the window - 1 keys before N."""

    name = "window"
    bucketed = False

    def __init__(self, cfg, stats: dict, window: int, ring: int):
        super().__init__(cfg, stats, PageSpace(
            cfg.num_window_pages, stats, "window_evictions", tiers=2), ring)
        self.window, self.ring, self.chunk = window, ring, cfg.chunk_size

    def held(self, req: _Request) -> int:
        return req.wlo + len(req.wpages)

    def _put(self, req: _Request, p: int, pid: int) -> None:
        req.wpages.append(pid)
        self.table[req.slot, p % self.ring] = pid

    def advanced(self, req: _Request, next_pos: int) -> None:
        """Give back req's pages every key of which is a window or more
        behind ``next_pos``, the oldest query still to be launched for it.
        From a booking: a dispatch in flight has its own copy of the ring
        and reads nothing behind its oldest query, and whoever gets the
        page next writes it in a later program."""
        self._return(req, min(
            max(next_pos - self.window + 1, 0) // self.page - req.wlo,
            len(req.wpages)))

    def _return(self, req: _Request, n: int) -> None:
        if n <= 0:
            return
        for i, pid in enumerate(req.wpages[:n]):
            self.space.unpin(pid, self.cold(req, req.wlo + i))
        del req.wpages[:n]
        req.wlo += n
        self.stats["window_pages_returned"] += n

    def cold(self, req: _Request, logical_page: int) -> bool:
        """Is a page worth less than the others once nobody holds it (the
        pool is a few windows a sequence)? Cold, reclaimed first: a page
        too far back to be in the tail of a prefix that ends within a
        window of this prompt's end — where a follow-up's shared prefix
        ends (the same document and another question, the next turn) —
        and a page in a sequence's first two windows, which guards a
        prefix that is cheap to compute again."""
        first_key = logical_page * self.page
        return (first_key + self.window < len(req.prompt_ids) - self.window
                or first_key + self.page <= 2 * self.window)

    def cut(self, hashes, n: int) -> tuple:
        """Cut a cached run of n full pages back to the longest
        whole-chunk prefix whose tail is cached too. Returns (its pages,
        the tail's first logical page, the tail's pages)."""
        per_chunk = self.chunk // self.page
        tail = -(-(self.window - 1) // self.page)
        while n > 0:
            lo = max(n - tail, 0)
            got = [self.space.hash_to_page.get(hashes[i])
                   for i in range(lo, n)]
            gone = [i for i, pid in enumerate(got) if pid is None]
            if not gone:
                return n, lo, got
            # no prefix whose tail holds the newest missing page is a hit
            n = (lo + gone[-1]) // per_chunk * per_chunk
        return 0, 0, []

    def map_in(self, req: _Request, lo: int, pids: list[int]) -> None:
        """Pin published pages as req's logical pages lo, lo + 1, ...;
        what is still to be computed is claimed dispatch by dispatch."""
        if not req.wpages:
            req.wlo = lo
        for i, pid in enumerate(pids):
            self.space.pin(pid)
            self._put(req, lo + i, pid)
        self.stats["window_pages_claimed"] += len(pids)

    def reuse_chunk(self, req: _Request, idxs: range, hashes) -> bool:
        """Mid-prefill reuse of the chunk of pages ``idxs``: its keys
        enter the window of what follows, so its pages are mapped in too
        (those before it are req's own already), or it is computed."""
        pids = [self.space.hash_to_page.get(hashes[i]) for i in idxs]
        if any(p is None for p in pids) or self.held(req) != idxs[0]:
            return False
        self.map_in(req, idxs[0], pids)
        self.advanced(req, idxs[-1] * self.page + self.page)
        return True

    def publish(self, req: _Request, lo: int, hi: int, hashes) -> None:
        """Of logical pages lo .. hi, the ones still held: the tail of a
        prefix that ends within a window of where the sequence stands."""
        for j in range(max(lo, req.wlo), min(hi, self.held(req))):
            self.space.publish(req.wpages[j - req.wlo], hashes[j])

    def release(self, req: _Request) -> None:
        self._return(req, len(req.wpages))
        req.wlo = 0

    def booked_prefill(self, rows) -> None:
        """The window layers' part of a prefill booking: the pages and
        (query, key) pairs the rows' window kernel swept, and the pages
        the rows have moved past, handed back."""
        st, pg, win = self.stats, self.page, self.window
        for req, pos, n in rows:
            st["prefill_ctx_wpages"] += (
                (pos + n - 1) // pg - max(pos - win + 1, 0) // pg + 1)
            # query q attends min(q + 1, window) keys
            ramp = min(max(win - 1 - pos, 0), n)
            st["prefill_attn_wpairs"] += (
                ramp * pos + ramp * (ramp + 1) // 2 + (n - ramp) * win)
            if req.slot >= 0:       # not retired since its launch
                self.advanced(req, pos + n)

    @staticmethod
    def live_pages(lengths, slots, page: int, window: int) -> int:
        """Pages that hold the ``window`` keys up to each slot's token."""
        return int(sum(
            int(n) // page - max(int(n) + 1 - window, 0) // page + 1
            for n in (lengths[sl] for sl in slots)))

    def launched_decode(self, lengths, slots) -> None:
        """Counted at the launch: what one decode step streams (live_pages)
        of the ring it runs."""
        self.stats["decode_live_wpages"] += self.live_pages(
            lengths, slots, self.page, self.window)
        self.stats["decode_table_wpages"] += self.table.size


class StateSlots(_Kind):
    """Recurrent-state layers (a gated delta rule's): what a sequence
    holds is a fixed-size state in its decode slot — row slot + 1 of the
    layers' device arrays, row 0 their sink — whatever its length: no page
    is claimed, none runs dry. ``space`` is the id space of the SNAPSHOT
    pool: copies of every state layer's state at one page boundary of a
    prompt, filed under that page's hash, parked and reclaimed as pages
    are. A cached prefix is a hit only as far as its newest snapshot
    (``newest``); the full pages beyond it are re-run. A prefill row loads
    and stores the slot's state or a snapshot inside its program, told by
    this kind's table (``rows``, a row [load, mode, snapshot from, store,
    snapshot to] — models/qwen3_next.py names the columns); a decode
    dispatch's table is the slots' rows alone. A snapshot is taken where
    a prompt's last whole page ends (``cut``: a later turn re-runs the
    previous answer, its new message and under a page), where a prefill
    dispatch leaves a prompt it has not finished (a long document asked
    again under another question resumes at most a dispatch short of what
    is shared), and nowhere in decode. A pool whose every snapshot is
    pinned refuses the snapshot, never the request.

    Which parked snapshot is reclaimed. A snapshot is of one of two KINDS
    by where it was taken, ``END`` (at ``cut``) or ``MID`` (a dispatch left
    the prompt unfinished there), parks in its kind's tier, LRU inside it,
    and goes back to that tier's tail when a hit lets go of it. Which kind
    requests come back for is the traffic's: sessions that resend their
    history resume the previous turn's END and never a MID; a document
    asked again under another question shares the document alone, so only
    a MID can serve it, beside streams whose ENDs nobody extends. So the
    order is learned from what admissions hit (``claim``): a resumed
    snapshot scores its kind, and so does the deepest GHOST (``ghosts``:
    the hash and kind of the last ``num_state_snapshots`` snapshots
    reclaimed; no device memory) on the request's chain between the
    newest snapshot and the end of its cached pages: the reclaimed
    snapshot the request would have resumed. Nothing shallower scores.
    The scores are held to a sum of the pool's size (both scaled down when
    a new one passes it), so the old ones fade and a change of traffic is
    followed. ``order``: the tier reclaimed from is the one with more
    residents per unit of score, len(tier) / (score + 1). A tie goes to
    MID, and with it the first reclaims of a pool nothing has scored in:
    a lost MID has another of its prompt a dispatch before it, a lost END
    re-runs a whole turn. So where only ENDs are ever hit, END's score
    settles at the pool's size and every MID goes before any END (PR 47's
    order); where only MIDs are, the dead ENDs go first; and where both
    are, each kind holds the pool by what was hit."""

    name = "state"
    bucketed = False
    CONTINUE, FRESH, RESUME, CHAIN = range(4)

    MID, END = 0, 1             # a snapshot's kind: its tier of ``space``

    def __init__(self, cfg, stats: dict):
        # a tier a kind. No fixed order serves both kinds of traffic: MID
        # always first is right for sessions (with one LRU tier the
        # ~2k-token snapshots of cold prompts push the ends out, every
        # miss re-runs a history in more dispatches, and those file more:
        # PERF.md §6, PR 47) and leaves a pool of dead ENDs under
        # documents asked twice beside short streams (no second ask ever
        # resumed: PERF.md §6, PR 51)
        super().__init__(cfg, stats, PageSpace(
            cfg.num_state_snapshots + 1, stats, "state_evictions",
            tiers=2), 1)
        self.on, self.size = bool(cfg.enable_prefix_caching), \
            cfg.num_state_snapshots
        self.kind = np.zeros((self.space.num_pages,), np.int8)
        self.score = [0.0, 0.0]
        self.ghosts: "OrderedDict[bytes, int]" = OrderedDict()
        self.space.order, self.space.heard = self.order, (self,)

    def order(self) -> list:
        """The snapshot pool's tiers, the one to reclaim from first."""
        mid, end = self.space.tiers
        if len(mid) * (self.score[self.END] + 1) >= \
                len(end) * (self.score[self.MID] + 1):
            return [mid, end]
        return [end, mid]

    def _scored(self, kind: int) -> None:
        """An admission came back for a snapshot of ``kind``."""
        self.score[kind] += 1
        total = sum(self.score)
        if total > self.size:
            self.score = [s * self.size / total for s in self.score]

    def published(self, sid: int, h: bytes, chain: int) -> None:
        self.ghosts.pop(h, None)            # filed again: no ghost

    def forgot(self, sid: int, h: bytes) -> None:
        """The pool reclaimed a parked snapshot: its ghost stays."""
        kind = int(self.kind[sid])
        self.stats[("state_evictions_mid", "state_evictions_end")[kind]] += 1
        self.ghosts[h] = kind
        if len(self.ghosts) > self.size:
            self.ghosts.popitem(last=False)

    def _park(self, sid: int) -> None:
        """Let go of a snapshot: into its own kind's tier, or free."""
        self.space.unpin(sid, cold=self.kind[sid] == self.MID)

    def held(self, req: _Request) -> int:
        return 1 << 30          # a state covers any length

    def ensure(self, req: _Request, upto_tokens: int) -> bool:
        return True

    def newest(self, hashes, n: int) -> int:
        """Pages of a cached run of ``n`` full pages that the newest
        snapshot on the chain covers (0: none)."""
        while n > 0 and hashes[n - 1] not in self.space.hash_to_page:
            n -= 1
        return n

    def claim(self, req: _Request, n_pages: int, found: int, hashes) -> None:
        """req was admitted, resuming behind ``n_pages`` of the ``found``
        full pages cached for it: its slot's row, the snapshot pinned
        until the row that loads it is launched, and the score of what it
        came back for (the class says)."""
        st = self.stats
        self.table[req.slot, 0] = req.slot + 1
        req.state_started = False
        if n_pages:
            req.state_snap = self.space.hash_to_page[hashes[n_pages - 1]]
            self.space.pin(req.state_snap)
            st["state_snapshot_hits"] += 1
            self._scored(int(self.kind[req.state_snap]))
        st["state_pages_untrusted"] += found - n_pages
        for at in range(found, n_pages, -1):
            kind = self.ghosts.get(hashes[at - 1])
            if kind is not None:
                st["state_ghost_hits"] += 1
                st["state_ghost_hit_tokens"] += (at - n_pages) * self.page
                self._scored(kind)
                break
        resumed = n_pages * self.page
        st["state_hit_tokens"] += resumed
        st["state_rerun_tokens"] += len(req.prompt_ids) - resumed

    def cut(self, req: _Request) -> int:
        """Where req's prefill takes its snapshot: the end of its prompt's
        last whole page, 0 for none (nothing whole, or where it resumed)."""
        at = len(req.prompt_ids) // self.page * self.page
        return at if at > req.prefix_tokens_saved else 0

    def rows(self, slots, cols: int, prefill) -> np.ndarray:
        """A decode's table: the slots' rows [n, 1]. A prefill's
        (``prefill``: its rows (req, start, tokens), fewer than ``slots``
        where the tail is padding): [n, 5] as the class says. The last row
        a request has in the dispatch stores its slot's state; that row
        (unless it ends the prompt) and the row that ends at ``cut`` also
        file a snapshot, if the pool has one to give."""
        if prefill is None:
            return super().rows(slots, 1, None)
        out = np.zeros((len(slots), 5), np.int32)
        for i, (req, pos, n) in enumerate(prefill):
            row = req.slot + 1
            out[i, 0] = row
            if i and prefill[i - 1][0] is req:
                out[i, 1] = self.CHAIN
            elif req.state_started:
                out[i, 1] = self.CONTINUE
            elif req.state_snap:
                out[i, 1], out[i, 2] = self.RESUME, req.state_snap
                # the program that reads it is launched: whoever takes
                # the snapshot's id next writes it in a later one
                self._park(req.state_snap)
                req.state_snap = 0
            else:
                out[i, 1] = self.FRESH
            req.state_started = True
            last = i + 1 == len(prefill) or prefill[i + 1][0] is not req
            end = pos + n
            if last:
                out[i, 3] = row
            if self.on and end // self.page not in req.state_taken and (
                    end == self.cut(req)
                    or last and end < len(req.prompt_ids)):
                if self.space.avail():
                    req.state_taken[end // self.page] = out[i, 4] = \
                        self.space.take()
                    self.stats["state_snapshots_taken"] += 1
                else:
                    self.stats["state_snapshots_refused"] += 1
        return out

    def publish(self, req: _Request, lo: int, hi: int, hashes) -> None:
        """The snapshots launched rows of req filed behind pages lo .. hi,
        now that those are booked: each under its last page's hash, then
        parked with its kind."""
        end = self.cut(req) // self.page
        for at in [a for a in req.state_taken if lo < a <= hi]:
            sid = req.state_taken.pop(at)
            self.kind[sid] = self.END if at == end else self.MID
            self.space.publish(sid, hashes[at - 1])
            self._park(sid)

    def release(self, req: _Request) -> None:
        # pinned and never loaded / filed and never booked
        for sid in (req.state_snap, *req.state_taken.values()):
            if sid:
                self._park(sid)
        req.state_snap = 0
        req.state_taken.clear()

    def booked_decode(self) -> None:
        self.stats["state_pool_live"] += (
            self.space.num_pages - 1 - len(self.space.free))


class KVCache:
    """The cache the engine holds: ``full`` pages always, ``window`` pages
    and ``state`` slots where ``model.cache_layers`` names such layers
    (``prefill_rows`` sizes a window's ring: window_need). The spill
    tier's hooks, where there is one: ``log.demote`` and ``promote`` (req,
    pages matched) -> pages brought back, which admission calls where the
    index's match ends."""

    def __init__(self, cfg, model, stats: dict, prefill_rows: int):
        self.cfg, self.stats = cfg, stats
        self.prefix_on = bool(cfg.enable_prefix_caching)
        self.promote = None
        self.full = FullPages(cfg, stats)
        # THE prefix index, which export, import and the spill tier ask
        # (one kind of page: refused over two, ROADMAP R2), and its log
        self.index, self.log = self.full.space, self.full.log
        # the kind each layer holds a sequence in, as the model says it
        self.layer_kinds = list(model.cache_layers(cfg.model))
        self.window: Optional[WindowPages] = None
        self.state: Optional[StateSlots] = None
        window = int(model.cache_window(cfg.model))
        ring, need = window_need(cfg, model, prefill_rows)
        if not window and cfg.num_window_pages:
            raise ValueError(
                "num_window_pages is for a model with sliding-window "
                f"layers; {type(cfg.model).__name__} has none")
        if cfg.num_window_pages < need:
            raise ValueError(
                f"num_window_pages={cfg.num_window_pages}: "
                f"{cfg.max_batch_size} sequences of a {window}-key window "
                f"need {need} pages of {cfg.page_size} (a ring of {ring} "
                "each)")
        if window:
            self.window = WindowPages(cfg, stats, window, ring)
        if "state" in self.layer_kinds:
            if window or cfg.spec_tokens:
                raise ValueError(
                    "recurrent-state layers beside sliding-window layers, "
                    "or under spec_tokens > 0 (a rejected draft has "
                    "already moved the state): neither is built")
            self.state = StateSlots(cfg, stats)
        elif cfg.num_state_snapshots:
            raise ValueError(
                "num_state_snapshots is for a model with recurrent-state "
                f"layers; {type(cfg.model).__name__} has none")
        self.kinds = [k for k in (self.full, self.window, self.state)
                      if k is not None]
        self.two_kinds = len(self.kinds) > 1
        # where a cached prefix may end: on a chunk (prefill resumes
        # there), or with state layers on any page — a snapshot lies where
        # a prompt's last whole page ends, and rows start where it does
        self.align = cfg.page_size if self.state else cfg.chunk_size

    def pool_args(self) -> dict:
        """What ``model.init_paged_cache`` is told beside the full pool:
        the sizes of the other kinds' pools."""
        out = {}
        if self.window is not None:
            out["window_pages"] = self.cfg.num_window_pages
        if self.state is not None:
            out.update(state_slots=self.cfg.max_batch_size,
                       state_snapshots=self.cfg.num_state_snapshots)
        return out

    # -- the index's key scheme ---------------------------------------------

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.cfg.page_size)

    def hash_chain(self, tokens, prev: bytes = b"") -> list[bytes]:
        """Chained content hashes of `tokens`' FULL pages from the seed
        ``prev``: equal keys imply equal whole prefixes. blake2b over the
        raw int32 bytes: stable across processes (PD payloads carry them)."""
        page, arr, out = self.cfg.page_size, np.asarray(tokens, np.int32), []
        for i in range(len(arr) // page):
            prev = hashlib.blake2b(
                prev + arr[i * page:(i + 1) * page].tobytes(),
                digest_size=16).digest()
            out.append(prev)
        return out

    def prompt_hashes(self, req: _Request) -> list[bytes]:
        if req.page_hashes is None:
            # the chain SEED is the request's prefix salt (empty for the
            # base model): an adapter's requests hash into a key space of
            # their own, so pages never match across tenants (different
            # adapters write different K/V for equal tokens)
            req.page_hashes = self.hash_chain(req.prompt_ids,
                                              prev=req.prefix_salt)
        return req.page_hashes

    def reuse_limit(self, n_prompt: int) -> int:
        """Most prompt tokens admissible from cache: aligned (``align``:
        prefill resumes on a chunk boundary, with state layers on a page's)
        and short of the prompt, so that the first generated token is
        sampled from real last-position logits."""
        return ((n_prompt - 1) // self.align) * self.align

    def hash_prompt(self, ids, salt: bytes = b"") -> list[bytes]:
        """Chained hashes of a prompt's admission-reusable pages: the
        whole full pages inside reuse_limit, the run match can admit."""
        limit = self.reuse_limit(len(ids))
        return self.hash_chain(ids[:limit], prev=salt) if limit > 0 else []

    def match(self, req: _Request) -> list[int]:
        """Longest cached run of full pages covering the prompt's head,
        cut to whole chunks and to reuse_limit. Pure lookup: no pin."""
        limit = self.reuse_limit(len(req.prompt_ids))
        if not self.prefix_on or limit <= 0:
            return []
        page = self.cfg.page_size
        pages = self.index.run(self.prompt_hashes(req)[:limit // page])
        per_chunk = self.align // page
        return pages[:(len(pages) // per_chunk) * per_chunk]

    # -- a request's pages ----------------------------------------------------

    def admit(self, req: _Request, slot: int) -> bool:
        """Admit req into ``slot``: match its prefix over every kind, pin
        what matched, claim pages for prompt + 1 (a request is held until
        the pool covers its whole prompt: no half-prefilled sequence can
        deadlock) and fill the tables — or False, with NO side effect."""
        st, chains = self.stats, self.log.chains
        matched = self.match(req)
        if self.promote is not None and \
                self.promote(req, len(matched)) > 0:
            # promoted pages are published and parked: re-walk, so the
            # match and the hit accounting see them as never evicted
            matched = self.match(req)
        found, lo, tail = len(matched), 0, []
        if self.window is not None and matched:
            n, lo, tail = self.window.cut(self.prompt_hashes(req), found)
            del matched[n:]
        if self.state is not None:
            # a hit reaches as far as the newest snapshot on the chain:
            # the full pages beyond it are re-run, not trusted
            del matched[self.state.newest(self.prompt_hashes(req), found):]
        if not self.full.claim(req, slot, matched,
                               self.pages_for(len(req.prompt_ids) + 1)):
            return False
        if tail:
            self.window.map_in(req, lo, tail)
        if self.window is not None and found > len(matched):
            st["prefix_tail_cut" if matched else "prefix_tail_lost"] += 1
            st["prefix_tail_tokens_lost"] += \
                (found - len(matched)) * self.cfg.page_size
        if chains is not None:
            hs = self.prompt_hashes(req)
            if hs:
                req.chain_slot = chains.slot_for(hs[0], req.prefix_salt)
        if matched:
            # chunked prefill starts at the first uncached chunk boundary
            req.prefill_pos = len(matched) * self.cfg.page_size
            req.prefix_tokens_saved = req.prefill_pos
            st["prefix_hits"] += len(matched)
            st["prefix_tokens_saved"] += req.prefill_pos
            if chains is not None:
                chains.hit(req.chain_slot, len(matched), req.prefill_pos)
        if self.state is not None:
            self.state.claim(req, len(matched), found,
                             self.prompt_hashes(req))
        return True

    def ensure(self, req: _Request, upto_tokens: int) -> bool:
        """Grow req's pages of every kind to cover upto_tokens."""
        return all(k.ensure(req, upto_tokens) for k in self.kinds)

    def held(self, req: _Request) -> int:
        """Logical pages req holds in every kind."""
        return min(k.held(req) for k in self.kinds)

    @property
    def reuses_mid_prefill(self) -> bool:
        """Can a request half-way through its prompt map in pages another
        has published since? Not over state layers: their state at that
        point is the request's own to compute."""
        return self.prefix_on and self.state is None

    def row_tokens(self, req: _Request, pos: int) -> int:
        """Tokens the prefill row of req that starts at ``pos`` carries: a
        chunk, the prompt's rest, or up to where a kind wants a row to end
        (the state layers' snapshot)."""
        n = min(self.cfg.chunk_size, len(req.prompt_ids) - pos)
        cut = self.state.cut(req) if self.state and self.prefix_on else 0
        return cut - pos if pos < cut < pos + n else n

    def reuse(self, req: _Request) -> None:
        """Mid-prefill reuse: jump req.prefill_pos over chunks whose pages
        another request has published since this one was admitted (an
        identical-prompt burst: the first prefills, the rest map its pages
        in as they land). Swapped-out private pages go to the free list."""
        c, page = self.cfg.chunk_size, self.cfg.page_size
        pos = req.prefill_pos
        if not self.reuses_mid_prefill or pos % c:
            return
        limit = self.reuse_limit(len(req.prompt_ids))
        hashes, sp = self.prompt_hashes(req), self.index
        while pos < limit:
            idxs = range(pos // page, (pos + c) // page)
            pids = [sp.hash_to_page.get(hashes[i]) for i in idxs]
            if any(p is None for p in pids) or not all(
                    k.reuse_chunk(req, idxs, hashes)
                    for k in self.kinds[1:]):
                break
            for i, pid in zip(idxs, pids):
                old = req.pages[i]
                if old != pid:
                    sp.pin(pid)
                    req.pages[i] = pid
                    sp.unpin(old)
            pos += c
            self.stats["prefix_hits"] += len(pids)
            self.stats["prefix_tokens_saved"] += c
            req.prefix_tokens_saved += c
            if self.log.chains is not None and req.chain_slot >= 0:
                self.log.chains.hit(req.chain_slot, len(pids), c)
        if pos != req.prefill_pos:
            req.prefill_pos = pos
            self.full.table[req.slot, :len(req.pages)] = req.pages

    def advanced(self, req: _Request, next_pos: int) -> None:
        """A booking moved req: ``next_pos`` is the oldest query still to
        be launched for it."""
        for k in self.kinds:
            k.advanced(req, next_pos)

    def booked_prefill(self, rows) -> None:
        """A prefill dispatch of ``rows`` (req, start, tokens) was booked:
        the full prompt pages it computed are misses, published at once
        (their K/V is written) so the rest of a burst can reuse them."""
        page = self.cfg.page_size
        for req, pos, n in (rows if self.prefix_on else ()):
            lo, hi = pos // page, (pos + n) // page
            self.stats["prefix_misses"] += hi - lo
            if self.log.chains is not None and hi > lo \
                    and req.chain_slot >= 0:
                self.log.chains.miss(req.chain_slot, hi - lo)
            for k in self.kinds:
                k.publish(req, lo, hi, self.prompt_hashes(req))
        for k in self.kinds:
            k.booked_prefill(rows)

    def launched_decode(self, lengths, slots) -> None:
        for k in self.kinds:
            k.launched_decode(lengths, slots)

    def booked_decode(self) -> None:
        for k in self.kinds:
            k.booked_decode()

    def release(self, req: _Request) -> None:
        """A retired request's pages go back, published first, and its
        tables' rows are zeroed: writes of the slot's next tenant through
        leftover entries would hit recycled pages."""
        if self.prefix_on:
            self._publish_retired(req)
        for k in self.kinds:
            k.release(req)
            if req.slot >= 0:
                k.table[req.slot, :] = 0

    def _publish_retired(self, req: _Request) -> None:
        """Publish req's full, KV-materialized pages: the prompt's and
        every generated token's but the last (a sampled token's K/V is
        written when it is fed back), so generated text is reusable by a
        multi-turn follow-up whose prompt embeds it."""
        page = self.cfg.page_size
        n_tok = len(req.prompt_ids) + max(len(req.out_ids) - 1, 0)
        if req.prefill_pos < len(req.prompt_ids):
            # released mid-prefill: only positions < prefill_pos hold
            # computed KV — more would serve garbage to matching prompts
            n_tok = req.prefill_pos
        n_full = min(n_tok // page, len(req.pages))
        if n_full <= 0:
            return
        hashes = self.prompt_hashes(req)
        if n_full > len(hashes):
            tokens = (req.prompt_ids + req.out_ids)[
                len(hashes) * page:n_full * page]
            hashes = hashes + self.hash_chain(
                tokens, prev=hashes[-1] if hashes else req.prefix_salt)
        if self.log.chains is not None and req.chain_slot < 0 and hashes:
            # short prompts never visited admission's chain assignment:
            # learn it here, or these pages' evictions fold to the sink
            req.chain_slot = self.log.chains.slot_for(hashes[0],
                                                      req.prefix_salt)
        for k in self.kinds:
            k.publish(req, 0, n_full, hashes)

    def tables(self, slots, width: int, prefill=None):
        """What a program takes as its block tables: the full table
        [n, width] of engine slots ``slots``, and with more kinds the
        tuple (full, the other kind's: a ring [n, ring], the state rows).
        A slot of -1 is a row of zeros (padding, an idle row, a
        warm-up's): its writes route to the sink. ``prefill``: the
        dispatch's rows (req, start, tokens), for a prefill program (a
        list, empty for a warm-up's); None for a decode's."""
        out = [k.rows(slots, width if k.bucketed else k.table.shape[1],
                      prefill) for k in self.kinds]
        return out[0] if len(out) == 1 else tuple(out)

    def take_unheld(self, hashes, reserve: int) -> list[tuple]:
        """Unheld pages for the hashes of a chain this cache lacks, as
        (index into hashes, page), while more than ``reserve`` pages stay
        allocatable. The caller fills, then parks them."""
        sp = self.index
        budget, took = sp.avail() - reserve, []
        for i, h in enumerate(hashes):
            if h in sp.hash_to_page:
                continue    # already cached locally (either source)
            if budget <= 0:
                break
            took.append((i, sp.take(refs=0)))
            budget -= 1
        return took

    def park(self, took, hashes, chain: int) -> None:
        """Publish filled pages (take_unheld) under their hashes and park
        them: the next match / reuse admits them like computed pages."""
        for i, pid in took:
            self.index.publish(pid, hashes[i], chain)
            self.index.lru[pid] = None

    def pool_stats(self) -> dict:
        """Free + cached are the allocatable pool: cached pages evict on
        demand, so a "full" pool with a deep cache is warm, not saturated."""
        sp = self.index
        out = {"free_pages": len(sp.free), "cached_pages": sp.parked(),
               "total_pages": sp.num_pages}
        if self.window is not None:
            wsp = self.window.space
            out.update(window_free_pages=len(wsp.free),
                       window_cached_pages=wsp.parked(),
                       window_total_pages=wsp.num_pages,
                       window_ring_pages=self.window.ring)
        if self.state is not None:
            ssp = self.state.space
            out.update(state_free_snapshots=len(ssp.free),
                       state_cached_snapshots=ssp.parked(),
                       state_total_snapshots=ssp.num_pages - 1)
        return out
