"""Per-layer metrics, one reader per file, found by the metric's name in
BENCHMARK.json. ``read(ctx) -> float | None``; a reader that finds nothing
to read returns None and the metric is left out of the line.

``ctx`` holds what the run gathered: ``records`` (client-side, requests due
in the window), ``stats_before`` / ``stats_after`` (the engine's counters at
the window's ends), ``trace`` (``reduce/xplane.py``'s reduction of the traced
slice, with the same counters at the slice's own ends under its
``stats_before`` / ``stats_after``: a reader that divides a counter by traced
time takes those, ``_engine.slice_deltas``), ``train`` (the trainer's final
report), ``device``, ``config``, ``traffic``. A metric that moves a different end-to-end metric in another
cell is a file of its own that imports the reader (``moves`` is one name
per metric)."""
