"""Per-layer metrics, one reader per file, found by the metric's name in
BENCHMARK.json. ``read(ctx) -> float | None``; a reader that finds nothing
to read returns None and the metric is left out of the line.

One entry a reader: its ``workloads`` lists every cell in which the reader
finds something to read, and a reader that serves configurations of
different shapes tells them apart by what the configuration's file says
(``experts_routed``, ``n_routed_experts``, ``moe_intermediate_size``),
never by a cell's name. ``moves`` is one name per metric, so a metric that
moves a different end-to-end metric in another cell is a file of its own
that imports the reader (``train_device_idle_share``, the pending chat
cells' ``chat_*``): the only two-line files here.

``ctx`` holds what the run gathered: ``records`` (client-side, requests due
in the window; ``all_records``: of the whole run), ``stats_before`` /
``stats_after`` (the engine's counters at the window's ends), ``trace``
(``reduce/xplane.py``'s reduction of the traced slice, with the same
counters at the slice's own ends under its ``stats_before`` /
``stats_after``: a reader that divides a counter by traced time takes
those, ``_engine.slice_deltas``), ``engine_ttft`` and ``serve_summary``
(``serve.metrics_summary()``'s engine TTFT and front-path groups, before
the first request and after the last: pairs), ``train`` (the trainer's
final report), ``device``, ``config``, ``traffic``."""
