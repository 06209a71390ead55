"""Which parked snapshot ``StateSlots`` reclaims (llm/kv_cache.py), on the
cache alone: no model, no device. Requests are admitted and booked as the
engine would, a dispatch of ``ROWS`` chunk-rows at a time, so that a long
prompt files a MID snapshot where each dispatch leaves it and every prompt
an END where its last whole page ends."""
import numpy as np
import pytest

from ray_tpu.llm.engine import _Request
from ray_tpu.llm.kv_cache import KVCache, STATE_COUNTERS
from ray_tpu.llm.paged_engine import PagedEngineConfig

PAGE, CHUNK, ROWS, POOL = 8, 32, 2, 8
MID, END = 0, 1


class _Hybrid:
    """As much of a model module as KVCache asks."""

    @staticmethod
    def cache_window(_):
        return 0

    @staticmethod
    def cache_layers(_):
        return ["state", "state", "state", "full"]


class _Spy:
    """Hears every reclaim: (the victim's kind, MIDs still parked)."""

    def __init__(self, state):
        self.state, self.seen = state, []

    def published(self, *_):
        pass

    def forgot(self, sid, h):
        self.seen.append((int(self.state.kind[sid]),
                          len(self.state.space.tiers[MID])))


def _cache(position_rule=False, **over):
    """A cache over a snapshot pool of POOL and full pages that never run
    out; ``position_rule``: the parent's fixed order, MID before END."""
    kw = dict(model=None, max_batch_size=2, page_size=PAGE, num_pages=8192,
              num_state_snapshots=POOL, max_pages_per_seq=256,
              chunk_size=CHUNK)
    kw.update(over)
    stats = dict.fromkeys(STATE_COUNTERS + (
        "prefix_hits", "prefix_misses", "prefix_evictions",
        "prefix_tokens_saved"), 0)
    cache = KVCache(PagedEngineConfig(**kw), _Hybrid, stats, ROWS)
    spy = _Spy(cache.state)
    cache.state.space.heard += (spy,)
    if position_rule:
        cache.state.space.order = None
    return cache, stats, spy


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


_ids = iter(range(1 << 30))


def _serve(cache, prompt) -> int:
    """One request from admission to release; -> prompt tokens resumed."""
    req = _Request(next(_ids), list(prompt), None)
    assert cache.admit(req, 0)
    resumed = pos = req.prefill_pos
    while pos < len(prompt):
        rows = []
        while pos < len(prompt) and len(rows) < ROWS:
            n = cache.row_tokens(req, pos)
            rows.append((req, pos, n))
            pos += n
        cache.tables([0] * len(rows), 256, prefill=rows)
        cache.booked_prefill(rows)
        req.prefill_pos = pos
    cache.release(req)
    space = cache.state.space
    assert not space.refs.any()
    assert len(space.free) + space.parked() == space.num_pages - 1
    return resumed


class _Traffic:
    """The two mixes, each drawing fresh prompts from its own seeds."""

    def __init__(self, cache, seed=0):
        self.cache, self.seeds = cache, iter(range(seed * 100000, 1 << 30))
        self.sessions = {}

    def fresh(self, n):
        return _tokens(n, next(self.seeds))

    def stream(self) -> int:
        """A one-dispatch prompt nobody extends: files a dead END."""
        return _serve(self.cache, self.fresh(40))

    def document(self, streams=10) -> int:
        """A document of four dispatches asked under two questions,
        ``streams`` short prompts in between; -> what the second ask
        resumed."""
        doc = self.fresh(200)
        assert _serve(self.cache, doc + self.fresh(20)) == 0
        for _ in range(streams):
            self.stream()
        return _serve(self.cache, doc + self.fresh(20))

    def turn(self, sid) -> int:
        """Session ``sid`` resends its history and a new message of two
        dispatches; -> what the turn resumed."""
        self.sessions[sid] = self.sessions.get(sid, []) + self.fresh(100)
        return _serve(self.cache, self.sessions[sid])

    def sessions_round(self, n=3) -> list:
        return [self.turn(sid) for sid in range(n)]


@pytest.mark.parametrize("streams", [8, 10, 16])
def test_documents_beside_dead_ends(streams):
    """(a) a document's second ask shares the document alone: only a MID
    serves it, and the streams' ENDs are what the pool gives up."""
    cache, st, _ = _cache()
    traffic = _Traffic(cache, seed=streams)
    resumed = [traffic.document(streams) for _ in range(8)]
    # a second ask resumes where the first ask's last dispatch inside the
    # document ended: 192 of its 200 tokens
    assert resumed[1:] == [192] * 7 and resumed[0] in (0, 192)
    assert st["state_snapshot_hits"] >= 7
    assert st["state_evictions_end"] > st["state_evictions_mid"]
    assert st["state_evictions"] == (st["state_evictions_mid"]
                                     + st["state_evictions_end"])
    # the parent's order never resumed one
    cache, st, _ = _cache(position_rule=True)
    traffic = _Traffic(cache, seed=streams)
    assert [traffic.document(streams) for _ in range(8)] == [0] * 8
    assert st["state_snapshot_hits"] == 0


@pytest.mark.parametrize("sessions", [2, 3])
def test_sessions_that_resend(sessions):
    """(b) a turn resumes the previous turn's END and nobody a MID: once
    ENDs have been hit, a parked MID is what every reclaim takes (PR 47's
    order), and the hits are that order's."""
    cache, st, spy = _cache()
    traffic = _Traffic(cache, seed=sessions)
    rounds = [traffic.sessions_round(sessions) for _ in range(12)]
    assert rounds[0] == [0] * sessions
    for r, got in enumerate(rounds[1:], 1):
        assert got == [100 * r // PAGE * PAGE] * sessions    # each its END
    # the pool's size of hits in, END's score is the pool's size: from
    # there no END goes while a MID is parked
    settled = POOL * 3
    assert cache.state.score[END] == POOL and cache.state.score[MID] == 0
    assert all(kind == MID or mids == 0 for kind, mids in spy.seen[settled:])
    assert st["state_evictions_mid"] > st["state_evictions_end"]
    parent, pst, _ = _cache(position_rule=True)
    traffic = _Traffic(parent, seed=sessions)
    assert [traffic.sessions_round(sessions) for _ in range(12)] == rounds
    assert pst["state_hit_tokens"] == st["state_hit_tokens"]


@pytest.mark.parametrize("ghosts", [True, False])
def test_the_traffic_changes(ghosts):
    """(c) sessions, then documents beside dead ends, on one cache. After
    the sessions END's score is the pool's size and a MID holds no place
    past the next snapshot: none lives to be hit, so no hit can say that
    one was wanted. A ghost can: the first document's second ask finds
    its, and the order turns within a pool's worth of admissions. With a
    ghost index that keeps nothing it never does."""
    cache, st, _ = _cache()
    traffic = _Traffic(cache, seed=7)
    for _ in range(8):
        traffic.sessions_round(3)
    assert cache.state.score == [0, POOL] and st["state_ghost_hits"] == 0
    hits = st["state_snapshot_hits"]
    if not ghosts:
        cache.state.ghosts = _Forgetful()
        assert [traffic.document(streams=1) for _ in range(8)] == [0] * 8
        assert st["state_snapshot_hits"] == hits
        assert st["state_ghost_hits"] == 0
        return
    # six admissions: the first document's second ask finds its ghost,
    # the second's its snapshot
    assert [traffic.document(streams=1) for _ in range(2)] == [0, 192]
    assert st["state_ghost_hits"] == 1
    assert st["state_ghost_hit_tokens"] == 192
    # and it stays turned, under the mix of (a)
    assert [traffic.document(streams=10) for _ in range(6)] == [192] * 6


class _Forgetful(dict):
    """A ghost index that keeps nothing."""

    def __setitem__(self, key, value):
        pass


@pytest.mark.parametrize("at,kind", [(128, MID), (136, END)])
def test_a_hit_snapshot_goes_to_the_tail_of_its_own_tier(at, kind):
    """(d) for either kind: pinned at admission it leaves its tier, and
    the row that loads it puts it back at that tier's tail (the parent
    put a hit MID among the ENDs)."""
    cache, _, _ = _cache()
    space = cache.state.space
    doc = _tokens(136, seed=1)          # 17 pages: MIDs at 64, 128, END 136
    _serve(cache, doc)
    _serve(cache, _tokens(136, seed=2))
    tier = space.tiers[kind]
    sid = space.hash_to_page[cache.hash_chain(doc)[at // PAGE - 1]]
    assert sid in tier and next(reversed(tier)) != sid
    before = [len(t) for t in space.tiers]
    req = _Request(next(_ids), doc[:at] + _tokens(20, seed=3), None)
    assert cache.admit(req, 0)
    assert (req.prefill_pos, req.state_snap) == (at, sid)
    assert sid not in space.tiers[MID] and sid not in space.tiers[END]
    cache.tables([0], 256, prefill=[(req, at, cache.row_tokens(req, at))])
    assert next(reversed(tier)) == sid
    assert [len(t) for t in space.tiers] == before
    cache.release(req)


def test_the_ghost_index_is_bounded_and_a_refiled_hash_leaves_it():
    """(e)"""
    cache, st, _ = _cache()
    traffic, state = _Traffic(cache), cache.state
    first = traffic.fresh(40)
    _serve(cache, first)
    h = cache.hash_chain(first)[4]
    for n in range(4 * POOL):
        traffic.stream()
        assert len(state.ghosts) == min(max(n + 2 - POOL, 0), POOL)
    assert st["state_evictions_end"] == 3 * POOL + 1
    assert h not in state.ghosts and h not in state.space.hash_to_page
    # a reclaimed prompt's ghost; filed again it is resident and no ghost
    again = traffic.fresh(40)
    _serve(cache, again)
    h = cache.hash_chain(again)[4]
    for _ in range(POOL):
        traffic.stream()
    assert h in state.ghosts and h not in state.space.hash_to_page
    _serve(cache, again)
    assert h not in state.ghosts and h in state.space.hash_to_page
    assert set(state.ghosts.values()) == {END}
    # a ghost holds no snapshot: the pool is whole
    assert len(state.space.free) + state.space.parked() == POOL
