"""Name the device's idle time by the program's own spans.

The program opens ``gopt.*`` spans (``jax.profiler.TraceAnnotation``) on
the thread that runs the work: ``gopt.wave`` around a served wave,
``gopt.execute`` inside it, the engine's phases (``gopt.pattern``,
``gopt.tail``, ``gopt.deliver``), one ``gopt.op.<KIND>`` per metered
operator, ``gopt.sync.<label>`` around each device->host scalar round trip
and ``gopt.d2h`` around each delivered column.  They sit in the same
``.xplane.pb`` as the device's programs, on the profiler's clock.

``split(path)`` reads one traced window and returns, besides
``trace_reduce``'s window and busy time (first device):

- ``idle_by_span``: the device's idle seconds in the window per name of
  the innermost ``gopt.*`` span open then, on whichever thread holds one
  (the latest-started span still open); empty on a trace with no program
  spans;
- ``idle_no_span_s``: the idle seconds with no ``gopt.*`` span open;
- ``idle_gaps``: ``idle_by_span`` merged with ``trace_reduce``'s naming
  (by the benchmark's ``bench.*`` spans) of the idle time no program span
  covers; on a trace with no program spans, ``trace_reduce.reduce``'s
  ``idle_gaps`` exactly;
- ``waves``: the ``gopt.wave`` spans that overlap the window;
  ``syncs`` and ``ops``: the ``gopt.sync.*`` and ``gopt.op.*`` spans
  inside those waves;
- ``busy_in_wave_s``: the device's busy seconds in the window that fall
  inside a ``gopt.wave`` span: how far the two clocks agree.

``for_run(ctx, root)`` finds the trace ``bench/run.py`` just wrote under
``<root>/.bench_trace`` and splits it once per process, for the readers
in ``bench/metrics/``.

    python bench/span_reduce.py <trace dir> [--plane /host:CPU]

prints the split of the newest trace under ``<trace dir>`` as JSON.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import heapq
import json
import os
from collections import defaultdict

import trace_reduce

PREFIX = "gopt."
TRACE_DIR = ".bench_trace"          # where bench/run.py writes its traces
# the idle metrics' groups of innermost span names
GROUPS = {
    "sync": lambda n: n.startswith("gopt.sync.") or n == "gopt.d2h",
    "ops": lambda n: n.startswith("gopt.op.") or n in (
        "gopt.pattern", "gopt.tail", "gopt.deliver"),
    "execute": lambda n: n == "gopt.execute",
    "wave": lambda n: n == "gopt.wave",
}


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read_events(path: str, device_plane: str = trace_reduce.DEVICE_PLANE):
    """``(modules, spans)`` in one pass over the trace: the device's
    program runs as ``(device, module, start_ns, end_ns)``, chosen as
    ``trace_reduce.read_events`` chooses them, and the benchmark's
    ``bench.*`` spans with the program's ``gopt.*`` spans, each as
    ``(thread, name, start_ns, end_ns)``.  A span's thread is
    ``<line name>#<line index>``: threads may share a name."""
    from jax.profiler import ProfileData
    modules, spans = [], []
    on_host = not device_plane.startswith("/device:")
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(device_plane)
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if ev.name.startswith((PREFIX, trace_reduce.SPAN_PREFIX)):
                    spans.append((f"{line.name}#{i}", ev.name, s, e))
                elif not on_device:
                    continue
                elif on_host:
                    if "hlo_op" in _stats(ev):   # XLA:CPU runs ops on threads
                        modules.append((plane.name, ev.name, s, e))
                elif line.name == "XLA Modules":
                    modules.append((plane.name, ev.name, s, e))
    return modules, spans


def innermost(spans, w0: float, w1: float) -> list:
    """``[(a, b, name | None)]``: ``[w0, w1]`` cut where the innermost open
    span changes, named by it (``None``: no span open)."""
    evs = []
    for i, (s, e, name) in enumerate(sorted(spans, key=lambda x: (x[0],
                                                                  -x[1]))):
        s, e = max(s, w0), min(e, w1)
        if e > s:
            evs.append((s, 1, i, name))
            evs.append((e, 0, i, name))
    evs.sort()
    out, heap, closed, t = [], [], set(), w0
    for when, kind, i, name in evs:
        while heap and heap[0][1] in closed:
            heapq.heappop(heap)
        if when > t:
            out.append((t, when, heap[0][2] if heap else None))
            t = when
        if kind:
            # the latest start is innermost; of equal starts, the later
            # (shorter) span in sorted order
            heapq.heappush(heap, (-when, -i, name))
        else:
            closed.add(-i)
    if w1 > t:
        out.append((t, w1, None))
    return out


def _overlap(a_iv, b_iv) -> list:
    """Intersections of two sorted lists of disjoint intervals; an
    interval of ``a_iv`` may carry a third item, which the output keeps."""
    out, j = [], 0
    for a in a_iv:
        while j < len(b_iv) and b_iv[j][1] <= a[0]:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < a[1]:
            s, e = max(a[0], b_iv[k][0]), min(a[1], b_iv[k][1])
            if e > s:
                out.append((s, e) + tuple(a[2:]))
            k += 1
    return out


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _BenchSpans:
    """``trace_reduce``'s naming of idle time by the benchmark's host
    spans: the serving loop's spans (``LOOP_SPANS``, one thread, never
    nested) name the time they cover; a part that none covers takes the
    name of another thread's span open at its midpoint, else
    ``"(no span)"``."""

    def __init__(self, spans):
        loop = sorted((s, e, n) for _, n, s, e in spans
                      if n in trace_reduce.LOOP_SPANS)
        self.loop = loop
        self.loop_starts = [x[0] for x in loop]
        self.other = sorted((s, e, n) for _, n, s, e in spans
                            if n not in trace_reduce.LOOP_SPANS
                            and n != trace_reduce.WINDOW_SPAN)
        self.other_starts = [x[0] for x in self.other]

    def split(self, a: float, b: float) -> dict:
        """Seconds of ``[a, b]`` (ns) per span name."""
        out = defaultdict(float)
        covered = 0.0
        j = max(bisect.bisect_right(self.loop_starts, a) - 1, 0)
        while j < len(self.loop) and self.loop[j][0] < b:
            s, e, name = self.loop[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov * 1e-9
                covered += ov
            j += 1
        rest = (b - a) - covered
        if rest > 0:
            mid = (a + b) / 2
            i = bisect.bisect_right(self.other_starts, mid) - 1
            name = next((self.other[k][2] for k in range(i, max(i - 16, -1),
                                                         -1)
                         if self.other[k][1] > mid), "(no span)")
            out[name] += rest * 1e-9
        return out


def _in_waves(spans, waves) -> list:
    """The spans that lie inside one of ``waves`` on the same thread."""
    by_thread = defaultdict(list)
    for th, _, s, e in sorted(waves, key=lambda x: x[2]):
        by_thread[th].append((s, e))
    starts = {th: [s for s, _ in iv] for th, iv in by_thread.items()}
    out = []
    for sp in spans:
        th, _, s, e = sp
        j = bisect.bisect_right(starts.get(th, []), s) - 1
        if j >= 0 and e <= by_thread[th][j][1]:
            out.append(sp)
    return out


def split(path: str, device_plane: str = trace_reduce.DEVICE_PLANE) -> dict:
    modules, spans = read_events(path, device_plane)
    windows = [(s, e) for _, n, s, e in spans if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} span in {path}")
    w0, w1 = windows[0]
    per_dev = defaultdict(list)
    for dev, _, s, e in modules:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            per_dev[dev].append((s, e))
    busy = _union(per_dev[sorted(per_dev)[0]]) if per_dev else []
    idle, t = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    prog = [(s, e, n) for _, n, s, e in spans if n.startswith(PREFIX)]
    by_span, gaps = defaultdict(float), defaultdict(float)
    bench = _BenchSpans([x for x in spans if not x[1].startswith(PREFIX)])
    no_span = 0.0
    for a, b, name in _overlap(innermost(prog, w0, w1), idle):
        if name is None:
            no_span += (b - a) * 1e-9
            for n, secs in bench.split(a, b).items():
                gaps[n] += secs
        else:
            by_span[name] += (b - a) * 1e-9
            gaps[name] += (b - a) * 1e-9
    # the waves that overlap the window, and the syncs and operators
    # inside them
    waves = [x for x in spans if x[1] == "gopt.wave" and x[3] > w0
             and x[2] < w1]
    inside = _in_waves([x for x in spans if x[1].startswith(
        ("gopt.sync.", "gopt.op."))], waves)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(e - s for s, e in busy) * 1e-9,
        "idle_s": sum(e - s for s, e in idle) * 1e-9,
        "idle_by_span": dict(by_span),
        "idle_no_span_s": no_span,
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
        "waves": len(waves),
        "syncs": sum(1 for x in inside if x[1].startswith("gopt.sync.")),
        "ops": sum(1 for x in inside if x[1].startswith("gopt.op.")),
        "busy_in_wave_s": sum(e - s for s, e in _overlap(
            busy, _union((s, e) for _, _, s, e in waves))) * 1e-9,
    }


@functools.lru_cache(maxsize=2)
def _split_file(path: str, _mtime_ns: int) -> dict:
    return split(path)


def for_run(ctx: dict, root) -> dict | None:
    """The split of the trace of the run whose reader context is ``ctx``:
    the newest trace under ``<root>/.bench_trace`` whose window is the one
    ``trace_reduce`` reduced; None when there is none."""
    tr = ctx.get("trace")
    files = glob.glob(os.path.join(str(root), TRACE_DIR, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not tr or not files:
        return None
    path = max(files, key=os.path.getmtime)
    sp = _split_file(path, os.stat(path).st_mtime_ns)
    if abs(sp["window_s"] - tr["window_s"]) > 1e-9 * max(tr["window_s"], 1):
        return None
    return sp


def idle_ms_per_wave(ctx: dict, root, group: str) -> float | None:
    """Device-idle ms per window wave while the innermost program span
    was one of ``GROUPS[group]``; None without program spans or waves."""
    sp = for_run(ctx, root)
    waves = len(ctx["waves"]["exec_s"])
    if sp is None or not sp["idle_by_span"] or not waves:
        return None
    inside = GROUPS[group]
    return 1e3 * sum(s for n, s in sp["idle_by_span"].items()
                     if inside(n)) / waves


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--plane", default=trace_reduce.DEVICE_PLANE)
    args = ap.parse_args(argv)
    print(json.dumps(split(trace_reduce.find_xplane(args.trace_dir),
                           args.plane), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
