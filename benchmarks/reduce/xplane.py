"""From a profiler trace to numbers: device busy and idle time, time per
jitted program and per operation, collectives exposed, and the longest
idle gaps. Every PR computes these the same way, from this file.

``load`` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into plain
lists; ``reduce`` works on those alone, so it can be checked on a small
recorded trace without jax (``testdata/``).

A device plane is named ``/device:TPU:<n>``. Its ``XLA Ops`` line holds one
event per executed HLO operation, named by the whole HLO instruction (a
Pallas kernel is a custom call whose target is ``tpu_custom_call``; a
``while`` encloses its body's operations); its ``XLA Modules`` line holds one
event per executed program, named ``<jit name>(<fingerprint>)``. Times count
from the start of the profiler session, on one clock for host and device.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv", re.I)


_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_META = re.compile(r"kernel_metadata=\{([^}]*)\}")
# the benchmark's own annotations, and the program's: the stepping thread's
# phases (``rtpu.engine.*`` / ``rtpu.loop.*``, util/profiling.phase) name
# what the host was doing in an idle gap
HOST_SPANS = re.compile(r"^(bench_|train_step$|report$|rtpu\.)")
# an engine decode program is named for the device steps one execution
# runs, its window: ``jit_rtpu_decode_w8``; any other program runs one
WINDOW = re.compile(r"_w(\d+)$")


def _describe(hlo: str) -> tuple:
    """An ``XLA Ops`` event is named by its whole HLO instruction:
    ``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=...``. Keep
    ``fusion.3:bf16[8,128]`` to show, and for matching the custom-call
    target (``tpu_custom_call`` is a Pallas kernel) and its metadata."""
    head, _, rest = hlo.partition(" = ")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    target = _TARGET.search(rest)
    meta = _META.search(rest)
    return (f"{head.lstrip('%')}:{shape}"[:100] if rest else hlo[:100],
            {"target": target.group(1) if target else "",
             "meta": meta.group(1)[:100] if meta else ""})


def load(path: str) -> list:
    """[{name, lines: [{name, events: [[name, start_ns, dur_ns, stats]]}]}]
    for the device planes of one ``.xplane.pb``, and the host plane's
    benchmark's and the program's annotations (``HOST_SPANS``) as the plane
    ``host``."""
    from jax.profiler import ProfileData
    planes, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [[ev.name, float(ev.start_ns), float(ev.duration_ns),
                          {}] for ev in line.events
                         if HOST_SPANS.match(ev.name)]
            continue
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name, stats = (_describe(ev.name) if line.name == OPS_LINE
                               else (ev.name, {}))
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    planes.append({"name": "host", "lines": [
        {"name": "annotations", "events": sorted(host, key=lambda e: e[1])}]})
    return planes


def _self_times(ops: list) -> list:
    """[(name, self_ns, stats)]: an operation that contains others (a
    ``while`` and its body's operations share the line) keeps only the time
    none of its children cover."""
    out, stack = [], []
    for n, s, d, st in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        out.append([n, d, st])
        stack.append((s + d, len(out) - 1))
    return out


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _subtract(a: list, b: list) -> float:
    """Total length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _program(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", name)


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _patterns(folder: str) -> dict:
    """{name: compiled pattern} from ``<folder>/<name>.json``.
    ``kernels/``: which operations belong to which hand-written kernel
    (matched against ``<op>:<shape> <custom-call target> <metadata>``).
    ``families/``: which program executions belong to which family (matched
    against the module's name followed by the Pallas calls inside it). A PR
    that adds a kernel or a program family adds a file."""
    import json
    out = {}
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), folder)
    for path in sorted(glob.glob(os.path.join(here, "*.json"))):
        with open(path) as f:
            out[os.path.basename(path)[:-5]] = re.compile(
                json.load(f)["pattern"], re.I)
    return out


def reduce(planes: list, chips: int = 1) -> dict:
    """The numbers every cell reports from its traced slice. Times are in
    seconds; per-device quantities are averaged over the devices that ran
    anything, except where the worst device is named."""
    families = _patterns("families")
    devices = []
    host = next((_line(p, "annotations") for p in planes
                 if p["name"] == "host"), [])
    for plane in planes:
        ops = _line(plane, OPS_LINE)
        if not ops or not plane["name"].startswith("/device:"):
            continue
        mods = _line(plane, MODULES_LINE)
        busy = _union([[s, s + d] for _, s, d, _ in ops])
        coll = _union([[s, s + d] for n, s, d, st in ops
                       if COLLECTIVE.search(n)])
        compute = _union([[s, s + d] for n, s, d, st in ops
                          if not COLLECTIVE.search(n)])
        by_op: dict = {}
        for n, d, st in _self_times(ops):
            rec = by_op.setdefault(n, [0.0, 0, st])
            rec[0] += max(d, 0.0)
            rec[1] += 1
        by_mod: dict = {}
        for n, s, d, _ in mods:
            by_mod.setdefault(_program(n), []).append(d)
        # a program execution is told by its name and the Pallas calls
        # that ran inside it
        kern = sorted([s, n] for n, s, d, st in ops
                      if st.get("target") == "tpu_custom_call")
        starts = [k[0] for k in kern]
        by_family: dict = {}
        family_steps: dict = {}
        for n, s, d, _ in mods:
            inside = {k[1] for k in kern[bisect.bisect_left(starts, s):
                                         bisect.bisect_right(starts, s + d)]}
            sig = " ".join([_program(n), *sorted(inside)])
            window = WINDOW.search(_program(n))
            for fam, rx in families.items():
                if rx.search(sig):
                    by_family.setdefault(fam, []).append(d)
                    family_steps[fam] = family_steps.get(fam, 0) + (
                        int(window.group(1)) if window else 1)
        gaps = [[busy[i][1], busy[i + 1][0]] for i in range(len(busy) - 1)]
        devices.append({
            "name": plane["name"], "first": busy[0][0], "last": busy[-1][1],
            "busy_ns": sum(e - s for s, e in busy),
            "collective_ns": sum(e - s for s, e in coll),
            "collective_exposed_ns": _subtract(coll, compute),
            "ops": by_op, "modules": by_mod, "families": by_family,
            "family_steps": family_steps,
            "gaps": gaps,
            "module_spans": [[_program(n), s, s + d] for n, s, d, _ in mods],
        })
    if not devices:
        return {"devices": 0}
    devices = devices[:chips] if chips else devices
    # the window runs from the first to the last device operation: the
    # profiler's own start and stop (50 ms and 230 ms of host work around
    # the training slice) are not the program's idle time
    first = min(d["first"] for d in devices)
    last = max(d["last"] for d in devices)
    window_ns = last - first
    worst = min(devices, key=lambda d: d["busy_ns"])
    ops_total: dict = {}
    for d in devices:
        for n, (ns, cnt, st) in d["ops"].items():
            rec = ops_total.setdefault(n, [0.0, 0, st])
            rec[0] += ns / len(devices)
            rec[1] += cnt
    mods_total: dict = {}
    fams_total: dict = {}
    steps_total: dict = {}
    for d in devices:
        for n, durs in d["modules"].items():
            mods_total.setdefault(n, []).extend(durs)
        for n, durs in d["families"].items():
            fams_total.setdefault(n, []).extend(durs)
            steps_total[n] = steps_total.get(n, 0) + d["family_steps"][n]
    # what the breakdown shows: operations of one kind and shape together
    # (a 20-layer program runs each under 20 names)
    shown: dict = {}
    for n, (ns, cnt, st) in ops_total.items():
        head, _, shape = n.partition(":")
        rec = shown.setdefault(
            f"{re.sub(r'[.][0-9]+$', '', head)}:{shape}", [0.0, 0, st])
        rec[0] += ns
        rec[1] += cnt

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2] if len(xs) % 2 else (
            xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2
    def stats_of(groups: dict) -> dict:
        return {n: {"count": len(d), "total_s": sum(d) / 1e9,
                    "median_s": median(d) / 1e9} for n, d in groups.items()}
    kernels = {}
    for group, rx in _patterns("kernels").items():
        hit = [(ns, cnt) for n, (ns, cnt, st) in ops_total.items()
               if rx.search(f"{n} {st.get('target', '')} "
                            f"{st.get('meta', '')}")]
        kernels[group] = {"seconds": sum(h[0] for h in hit) / 1e9,
                          "count": sum(h[1] for h in hit)}
    gaps = sorted(worst["gaps"], key=lambda g: g[0] - g[1])[:5]
    named_gaps = []
    for s, e in gaps:
        before = [m for m in worst["module_spans"] if m[2] <= s + 1]
        after = [m for m in worst["module_spans"] if m[1] >= e - 1]
        mid = (s + e) / 2
        named_gaps.append({
            "start_ns": s, "end_ns": e, "seconds": (e - s) / 1e9,
            "host": sorted({n for n, hs, hd, _ in host
                            if hs <= mid < hs + hd
                            and not n.startswith("bench_clock_sync")}),
            "after": max(before, key=lambda m: m[2])[0] if before else "",
            "before": min(after, key=lambda m: m[1])[0] if after else ""})
    # the replica notes the wall clock inside an annotation named for it:
    # wall = trace + offset
    sync = next(([n, hs] for n, hs, _, _ in host
                 if n.startswith("bench_clock_sync.")), None)
    return {
        "clock_offset_ns": (int(sync[0].rsplit(".", 1)[1]) - sync[1]
                            if sync else None),
        "devices": len(devices),
        "window_s": window_ns / 1e9,
        "first_ns": first,
        "busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
        "busy_s_worst": worst["busy_ns"] / 1e9,
        "collective_s": max(d["collective_ns"] for d in devices) / 1e9,
        "collective_exposed_s": max(
            d["collective_exposed_ns"] for d in devices) / 1e9,
        "ops": sorted(([n, ns / 1e9, cnt, st.get("target", "")]
                       for n, (ns, cnt, st) in shown.items()),
                      key=lambda r: -r[1])[:20],
        "modules": stats_of(mods_total),
        # ``steps``: the device steps the family's executions ran, a decode
        # program's window each: total_s / steps is one number whatever
        # the mixture of ``decode_w1`` and ``decode_w8`` in the slice
        "families": {n: dict(st, steps=steps_total[n])
                     for n, st in stats_of(fams_total).items()},
        "kernels": kernels,
        "idle_gaps": named_gaps,
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    """Reduce the newest trace under ``trace_dir``, then delete it (traces
    are large)."""
    import shutil
    out = reduce(load(find_xplane(trace_dir)), chips)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out
