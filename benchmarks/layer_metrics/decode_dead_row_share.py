"""Engine scheduler (llm/paged_engine.py ``step()`` / ``_book_decode``): the
share of the decode program's rows x steps that ran for a request the
booking before them had found done. A decode is launched behind the decode
before it, unbooked, and a stop the host cannot foresee (end of sequence, a
stop token) is seen one dispatch late: that row runs one dead dispatch,
whose tokens are thrown away. Counters ``decode_dead_rows`` /
(``decode_steps`` x the configuration's ``max_batch_size``) over the window;
None on a program without the counter."""
from ._engine import per


def read(ctx: dict):
    rows = per(ctx, "decode_dead_rows", "decode_steps")
    if rows is None:
        return None
    return 100.0 * rows / ctx["config"]["engine"]["max_batch_size"]
