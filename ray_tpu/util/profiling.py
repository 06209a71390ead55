"""JAX step profiling: compile-vs-execute wall split, FLOPs -> MFU.

The measurement layer the ROADMAP's TPU goals (MFU closure, TTFT) report
through, so the numbers come from the framework rather than ad-hoc bench
scripts (the Gemma-on-TPU comparison papers only trust MFU/TTFT claims
whose methodology ships with the system). Three pieces:

- :class:`StepProfiler` — per-step wall-clock accounting with the
  compile/execute split. jit functions compile on FIRST call per static
  key (shape bucket, sampling mode), so the profiler attributes the
  first observation of each key to compile time and the rest to execute
  time; callers that know better (paged_engine.warmup) record compiles
  explicitly. Every step also lands in the flight recorder
  (STEP_BEGIN/STEP_END), so step cadence shows up on the cluster
  timeline next to the channel/dispatch events.
- FLOPs estimation — ``compiled_flops(fn, *args)`` lowers+compiles a
  jitted function out of band and reads XLA's ``cost_analysis()``;
  :func:`mfu` divides by wall time and the device's peak. Peak FLOPs
  come from the one device-kind table (parallel.mesh.DEVICE_PEAKS; off
  TPU -> None, MFU then reports None rather than a made-up number; an
  unlisted TPU kind is an error).
- :class:`phase` — the one primitive the serving loop's host phases are
  timed with (llm/paged_engine.py ``step()``, llm/serving.py ``_loop``):
  a ``jax.profiler.TraceAnnotation`` (a span on the host plane of the
  profiler's trace, on the device operations' clock, when a profiler
  session is active; an object built and dropped when none is) plus
  the elapsed nanoseconds added to a counter. Always on: no flag, no
  config field.

Both are cheap enough to leave attached. A ``StepProfiler.step`` is two
perf_counter reads and two flight events around a jitted call; a
``phase`` is two reads of the wall clock (a vDSO read, 0.1 us), one
annotation and two or three dict updates, some twelve times a
``step()`` of the engine. Neither reads the thread's CPU clock: that is
a system call (0.3 us on a plain kernel, 6 us under gVisor, where the
chip's machines run), and who wants a thread's CPU time reads it from
outside the thread (llm/serving.py ``engine_stats``). FLOPs estimation
triggers an extra XLA compile, so it runs only when explicitly
requested.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Optional

from ..core import flight

# StepProfiler kind codes for the flight ring (exported by name)
STEP_KINDS = {"prefill": 0, "decode": 1, "verify": 2, "update": 3,
              "train": 4, "other": 5}


def device_peak_flops(device=None) -> Optional[float]:
    """Per-device peak bf16 FLOP/s from THE peaks table
    (parallel.mesh.DEVICE_PEAKS). None off-TPU — MFU then reports None
    rather than a share of a nominal number; an unlisted TPU kind
    raises."""
    import jax

    from ..parallel.mesh import device_peak
    device = device or jax.devices()[0]
    return device_peak(device)[1] if device.platform == "tpu" else None


def _flops_of(compiled) -> Optional[float]:
    """Pull the 'flops' entry out of a compiled executable's
    cost_analysis(), tolerating the per-version shapes jax has used
    (dict, list-of-dicts per computation)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None  # backend without cost analysis: FLOPs unknown
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    val = ca.get("flops")
    return float(val) if val else None


def compiled_flops(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs per invocation of a jit-wrapped ``fn`` at these arg shapes,
    via an out-of-band lower+compile (costs one extra XLA compile — call
    once, cache the result). None when fn isn't jitted or XLA won't
    say."""
    try:
        lowered = fn.lower(*args, **kwargs)
        return _flops_of(lowered.compile())
    except Exception:
        return None  # not a jit fn / lowering failed: FLOPs unknown


def mfu(flops_per_step: Optional[float], step_seconds: float,
        n_devices: int = 1, peak: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization for one step, or None when either the
    FLOPs or the device peak is unknown."""
    peak = peak if peak is not None else device_peak_flops()
    if not flops_per_step or not peak or step_seconds <= 0:
        return None
    return flops_per_step / (step_seconds * peak * max(1, n_devices))


class StepProfiler:
    """Wall-clock accounting for a family of jitted steps.

    ``with prof.step("decode"):`` times one step; the first step seen
    for a (kind, key) pair is booked as compile time (jit compiles on
    first call per static key), later ones as execute time.
    ``record_compile`` books an explicitly measured compile (warmup
    paths). ``attach_flops`` stores a FLOPs-per-step estimate so
    ``summary()`` can report MFU.
    """

    def __init__(self, name: str = "step", n_devices: int = 1):
        self.name = name
        self.n_devices = max(1, n_devices)
        self.compile_s = 0.0
        self.execute_s = 0.0
        self.compiles = 0
        self.steps = 0
        self.flops_per_step: dict[str, float] = {}
        self.steps_by_kind: dict[str, int] = {}
        self._steps_by_tag: dict[tuple, int] = {}
        self._flops_by_tag: dict[tuple, float] = {}
        self._seen: set = set()
        self._peak = device_peak_flops()

    @contextlib.contextmanager
    def step(self, kind: str = "other", key: Any = None):
        code = STEP_KINDS.get(kind, STEP_KINDS["other"])
        flight.evt(flight.STEP_BEGIN, code)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            flight.evt(flight.STEP_END, code)
            tag = (kind, key)
            if tag not in self._seen:
                # first call at this static key: XLA compiled inside it
                self._seen.add(tag)
                self.compile_s += dt
                self.compiles += 1
            else:
                self.execute_s += dt
                self.steps += 1
                self.steps_by_kind[kind] = \
                    self.steps_by_kind.get(kind, 0) + 1
                self._steps_by_tag[tag] = \
                    self._steps_by_tag.get(tag, 0) + 1

    def record_compile(self, seconds: float, kind: str = "other",
                       key: Any = None) -> None:
        """Book an explicitly measured compile (e.g. warmup) and mark
        its key warm so the next timed step counts as execute."""
        self.compile_s += seconds
        self.compiles += 1
        self._seen.add((kind, key))

    def attach_flops(self, kind: str, flops: Optional[float],
                     key: Any = None) -> None:
        """Record a FLOPs-per-step estimate for steps of ``(kind, key)``.
        The key must be the SAME static key those steps time under: a
        jitted program's cost is a function of its static shapes, so an
        estimate taken at one shape must not be credited to dispatches
        at another (an 8-row prefill estimate applied to 1-row steps
        would inflate MFU ~8x). Steps at unestimated keys contribute
        wall but no FLOPs — MFU understates, never overstates.

        ``summary()['flops_per_step'][kind]`` keeps the LARGEST estimate
        attached for the kind (the widest program) as the representative
        per-step cost — with several keys per kind (page buckets) the
        last-attached key would otherwise win arbitrarily; MFU always
        uses the exact per-tag estimates regardless."""
        if flops:
            self.flops_per_step[kind] = max(
                float(flops), self.flops_per_step.get(kind, 0.0))
            self._flops_by_tag[(kind, key)] = float(flops)

    def summary(self) -> dict:
        per_step = (self.execute_s / self.steps) if self.steps else None
        # MFU over the whole execute window: flops actually performed
        # (per-(kind, static-key) flops x matching executed steps) over
        # total execute wall — NOT sum-of-all-kind flops over the
        # mixed-kind average step, and NOT full-shape estimates credited
        # to smaller-shape dispatches; either would inflate. Steps at
        # unestimated tags contribute wall but no flops, so a partial
        # estimate UNDERstates MFU (honest direction).
        done_flops = sum(
            f * self._steps_by_tag.get(tag, 0)
            for tag, f in self._flops_by_tag.items()) or None
        return {
            "name": self.name,
            "compile_s": round(self.compile_s, 6),
            "execute_s": round(self.execute_s, 6),
            "compiles": self.compiles,
            "steps": self.steps,
            "steps_by_kind": dict(self.steps_by_kind) or None,
            "step_wall_s": per_step,
            "flops_per_step": self.flops_per_step or None,
            "peak_flops": self._peak,
            "mfu": (mfu(done_flops, self.execute_s, self.n_devices,
                        self._peak)
                    if self.execute_s else None),
        }


_annotation = None     # jax.profiler.TraceAnnotation, resolved on first use


class phase:
    """``with phase(stats, "ns_admit", "rtpu.engine.admit"):`` — one
    host phase of a loop, recorded into two sinks at once:

    - a ``jax.profiler.TraceAnnotation(name)``: with a profiler session
      active the span lands on the host plane of the same xplane as the
      device operations, on their clock, so an idle gap on the device
      can be named by the phase the host was in; with none active the
      object is built and dropped;
    - ``stats[key]`` grows by the elapsed ``perf_counter_ns``, and
      ``stats["max_" + key]`` keeps the longest single occurrence (a
      stall names its phase; the ``max_`` prefix keeps a sum over every
      ``ns_*`` key from adding a maximum);
    - ``stats[also]``, where given, grows by the same amount: one
      occurrence counted under a second heading (the engine's launches,
      which share ``ns_<family>_device`` with the readback waits).

    The clock is read first on entry and last on exit, so consecutive
    phases leave only the interpreter's own call overhead between them.
    jax is imported on first use (``import ray_tpu`` stays light)."""

    __slots__ = ("_stats", "_key", "_name", "_also", "_ann", "_t0")

    def __init__(self, stats: dict, key: str, name: str,
                 also: Optional[str] = None):
        self._stats, self._key, self._name = stats, key, name
        self._also = also

    def __enter__(self):
        global _annotation
        self._t0 = time.perf_counter_ns()
        if _annotation is None:
            import jax
            _annotation = jax.profiler.TraceAnnotation
        self._ann = _annotation(self._name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        stats, key = self._stats, self._key
        dt = time.perf_counter_ns() - self._t0
        stats[key] = stats.get(key, 0) + dt
        if dt > stats.get("max_" + key, 0):
            stats["max_" + key] = dt
        if self._also is not None:
            stats[self._also] = stats.get(self._also, 0) + dt
        return False
