"""The repository's benchmark: one cell per invocation of ``benchmarks.run``.

Everything that decides a number lives here (traffic generation, the
reduction from traces and counters to metrics, the peaks table, FLOP and
byte arithmetic, the plain references, the comparison behind ``correct``),
so that a later PR can change the program but not its yardstick. From the
program it takes only the system under test — ``ray_tpu.init``,
``serve.run``, ``JaxTrainer`` — and its counters and kernel names.
"""
