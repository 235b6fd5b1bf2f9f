"""Profiler trace to device time: capture, compact form, reduction.

``capture(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps only what the metrics need, in a compact form that tests can hold:

  {"device": [{"modules": [[name, start_ns, dur_ns], ...],
               "ops":     [[label, start_ns, dur_ns, kind], ...]}, ...],
   "host":   [[name, start_ns, dur_ns], ...]}     # the harness's spans

An op's ``label`` is its HLO name and result shape (``%copy.116 =
bf16[32,32,40,256,96]``); its ``kind`` is the opcode, with the target of
a custom call (``custom-call:tpu_custom_call`` is a Pallas kernel) and the
kind of a fusion (``fusion:kLoop``).

``reduce(events, ...)`` turns that into busy and idle time, device time
per program, kernel time, the operations that took most time and the
longest idle gaps, each gap named by the harness span it fell in.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence

HOST_PREFIX = "bench."
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")   # hold other ops' time


_KIND = re.compile(r"^\S+ = (?:\S+|\(.*?\)) ([a-z][\w-]*)\(")


def op_label(text: str) -> List[str]:
    """[label, kind] of an XLA op's event name (its HLO text)."""
    label = text.split("{", 1)[0].strip()[:160]
    m = _KIND.match(text)
    kind = m.group(1) if m else "?"
    if kind == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', text)
        kind += ":" + (t.group(1) if t else "?")
    elif kind == "fusion":
        k = re.search(r"kind=(k\w+)", text)
        kind += ":" + (k.group(1) if k else "?")
    return [label, kind]


def capture(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"device": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = {"plane": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(
                    line.name)
                if key == "modules":
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
                elif key == "ops":
                    dev[key] = [
                        [*op_label(e.name)[:1], int(e.start_ns),
                         int(e.duration_ns), op_label(e.name)[1]]
                        for e in line.events]
            out["device"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    out["host"].sort(key=lambda e: e[1])
    return out


def _union(intervals: List[Sequence[float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _span_at(host, s, e) -> str:
    """The harness span that overlaps [s, e] the most."""
    best, name = 0.0, "no harness span"
    for n, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def reduce(events: Dict, *, programs: Dict[str, str],
           kernels: Dict[str, str], top: int = 10) -> Dict:
    """Busy and idle time over the traced window, per-program and
    per-kernel device time. ``programs`` and ``kernels`` map a metric's
    name to a regular expression on module and op names. The window runs
    from the first to the last harness span. Times are seconds."""
    host = events["host"]
    if not host or not events["device"]:
        return {}
    lo = min(h[1] for h in host)
    hi = max(h[1] + h[2] for h in host)
    window = (hi - lo) * 1e-9
    per_dev = []
    op_time: Dict[str, float] = {}
    gaps = []
    prog: Dict[str, List[float]] = {k: [] for k in programs}
    kern: Dict[str, List[List[float]]] = {k: [] for k in kernels}
    for dev in events["device"]:
        ops = [[s, s + d] for _, s, d, _ in dev["ops"]]
        busy_iv = _clip(_union(ops), lo, hi)
        per_dev.append(sum(e - s for s, e in busy_iv) * 1e-9)
        for label, s, d, kind in dev["ops"]:
            if s >= lo and s + d <= hi:
                name = f"{label} {kind}"
                if kind not in CONTAINERS:
                    op_time[name] = op_time.get(name, 0.0) + d * 1e-9
                for k, pat in kernels.items():
                    if re.search(pat, name):
                        kern[k].append([s, d])
        for name, s, d in dev["modules"]:
            if s >= lo and s + d <= hi:
                for k, pat in programs.items():
                    if re.search(pat, name):
                        prog[k].append(d * 1e-9)
        edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    return {
        "window_s": window,
        "busy_s": sum(per_dev) / len(per_dev),
        "programs": {k: {"calls": len(v), "seconds": sum(v)}
                     for k, v in prog.items()},
        "kernels": {k: {"calls": len(v), "seconds": sum(d for _, d in v)
                        * 1e-9, "events": v} for k, v in kern.items()},
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_span_at(host, s, e), g * 1e-9]
                      for g, s, e in gaps[:top]],
    }


def kernel_time_in(reduced: Dict, kernel: str, spans) -> List[float]:
    """Seconds of ``kernel`` inside each of ``spans`` ([start_ns, dur_ns])."""
    ev = reduced["kernels"][kernel]["events"]
    out = []
    for s, d in spans:
        out.append(sum(kd for ks, kd in ev if ks >= s and ks < s + d) * 1e-9)
    return out
