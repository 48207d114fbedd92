"""Reduce a JAX profiler trace of one window to the benchmark's numbers.

``reduce(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
returns, over the window that the benchmark's ``bench.window`` host span
marks:

- ``window_s`` and ``busy_s``: the window's length, and the union of the
  intervals in which an operation ran on a device, averaged over the
  devices that ran any (one chip: that chip's busy time);
- ``device_ops``: device seconds per XLA module (program), summed over
  the window's runs; ``module_s`` holds all of them and ``op_s`` the
  seconds per operation inside them (a Pallas kernel is an operation
  named after the kernel);
- ``idle_gaps``: the device's idle time within the window, named by
  the benchmark's host span (``bench.*``) open at the time on the serving
  loop, else on another thread, else ``"(no span)"``; seconds summed
  per name.

On a TPU trace each chip is a plane ``/device:TPU:<n>`` whose ``XLA
Modules`` line holds the program runs (busy time is their union) and
whose ``XLA Ops`` line holds the operations.  On a CPU-only trace, where
XLA runs its ops on host threads, pass ``device_plane="/host:CPU"``: the
events that carry an ``hlo_op`` statistic are then both.  A CPU trace
tests the arithmetic; its numbers are no device numbers.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_OP_SUFFIX = re.compile(r"\.\d+$")
SPAN_PREFIX = "bench."
# the serving loop's spans win when spans of several threads are open
LOOP_SPANS = ("bench.step", "bench.flush", "bench.idle", "bench.deliver")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read_events(path: str, device_plane: str = DEVICE_PLANE):
    """``(modules, ops, spans)``: the device's program runs as ``(device,
    module, start_ns, end_ns)``, its operations as ``(device, op,
    start_ns, end_ns)``, and the benchmark's host spans as ``(thread,
    name, start_ns, end_ns)``.  Module names drop XLA's ``(fingerprint)``
    suffix; op names keep the part before `` = `` without its ``.N``
    suffix (``%wcoj_intersect.1 = ...`` is ``%wcoj_intersect``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    for plane in pd.planes:
        on_device = plane.name.startswith(device_plane)
        for line in plane.lines:
            kind = line.name if device_plane.startswith("/device:") \
                else "host"
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((line.name, ev.name, start, end))
                elif not on_device:
                    continue
                elif kind == "XLA Modules":
                    modules.append((plane.name, _FINGERPRINT.sub("", ev.name),
                                    start, end))
                elif kind == "XLA Ops":
                    ops.append((plane.name, _op_name(ev.name), start, end))
                elif kind == "host":
                    st = _stats(ev)
                    if "hlo_op" in st:          # XLA:CPU runs ops on threads
                        modules.append((plane.name, str(st.get("hlo_module")),
                                        start, end))
                        ops.append((plane.name, _op_name(ev.name), start,
                                    end))
    return modules, ops, spans


def _op_name(text: str) -> str:
    return _OP_SUFFIX.sub("", text.split(" = ")[0].strip())


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Spans:
    """The benchmark's host spans, to name what the host was doing while
    the device sat idle.  The serving loop's spans (``LOOP_SPANS``, one
    thread, never nested) name the time they cover; a part of a gap that
    none covers takes the name of another thread's span open at its
    midpoint, else ``"(no span)"``."""

    def __init__(self, spans):
        loop = sorted((s, e, n) for _, n, s, e in spans if n in LOOP_SPANS)
        self.loop = loop
        self.loop_starts = [x[0] for x in loop]
        self.other = sorted((s, e, n) for _, n, s, e in spans
                            if n not in LOOP_SPANS and n != WINDOW_SPAN)
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


def reduce(path: str, device_plane: str = DEVICE_PLANE, top: int = 10) -> dict:
    modules, ops, spans = read_events(path, device_plane)
    windows = [(s, e) for _, name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = windows[0]

    def clipped(events):
        for dev, name, s, e in events:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                yield dev, name, s, e
    per_dev = defaultdict(list)
    per_module = defaultdict(float)
    for dev, name, s, e in clipped(modules):
        per_dev[dev].append((s, e))
        per_module[name] += (e - s) * 1e-9
    per_op = defaultdict(float)
    for _, name, s, e in clipped(ops):
        per_op[name] += (e - s) * 1e-9
    busy = {d: _union(iv) for d, iv in per_dev.items()}
    busy_s = (sum(sum(e - s for s, e in u) for u in busy.values())
              / max(len(busy), 1)) * 1e-9
    gaps = defaultdict(float)
    where = _Spans(spans)
    # idle gaps of the first device (one chip: the chip)
    first = busy[sorted(busy)[0]] if busy else []
    t = w0
    for s, e in first + [[w1, w1]]:
        if s > t:
            for name, secs in where.split(t, s).items():
                gaps[name] += secs
        t = max(t, e)

    def rank(d):
        return sorted(([k, v] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_s,
            "devices": len(busy), "n_ops": sum(map(len, per_dev.values())),
            "device_ops": rank(per_module), "idle_gaps": rank(gaps),
            "module_s": dict(per_module), "op_s": dict(per_op)}
