"""End-to-end metrics, one reader per file, found by the metric's name in
BENCHMARK.json. ``read(ctx) -> float | None``: None leaves the metric out
of the line. All are taken on the benchmark's own host clock."""
