"""One CPU rehearsal of doc-QA (``--trace 1``) whose line holds all ten
readers of the front path's clock (PR 53; their arithmetic:
``test_front_stage_readers.py``): the series came through
``kinds/serve_child.py``'s ``stats`` event — proxy, router, model replica,
both rings — with counts that cover the run's requests."""
from bh_util import rehearse
from test_front_stage_readers import NAMES


def test_docqa_rehearses_with_all_ten_in_its_traced_line():
    """Through the real path on the CPU: proxy, router, model replica,
    both rings, ``serve_child``'s ``stats`` event. A name is in a
    rehearsal's line only where its reader found something to read (the
    value itself is nulled: no CPU number under a device metric's name)."""
    line = rehearse("docqa-sessions-1chip", trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert set(NAMES) <= set(line["metrics"]), set(NAMES) - set(
        line["metrics"])
    # and the readers they are reckoned against
    assert {"front_overhead_ms", "router_wait_ms",
            "first_chunk_lag_ms"} <= set(line["metrics"])

