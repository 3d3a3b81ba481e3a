"""From a JAX profiler trace to the benchmark's device numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain data, ``{plane: {line: [(name, start_ns, duration_ns), ...]}}``;
``reduce`` works on that data alone, so it can be checked on a small
recorded trace. What ``reduce`` reads:

- the measured window: the host span ``bench.window`` that the harness
  opens around the traced part of its window;
- device busy time: the union of the intervals of the ``XLA Ops`` events
  of each device plane, clipped to the window, averaged over devices;
- the router step's device time: the ``XLA Modules`` events whose name
  is the step program's (``<module>`` or ``<module>(<id>)``);
- the largest top-level device operations by time (an operation that
  runs inside a loop operation counts as part of the loop), and the
  idle gaps between operations, each charged to the innermost host span
  in progress at its midpoint, else ``no_span``: the benchmark's own
  ``lane.submit`` and ``lane.collect`` around the model's calls, and the
  lane pump's phases ``lane.idle`` (no message to submit), ``lane.gate``
  (the pipeline full, messages waiting on a collect) and
  ``lane.respond`` (the answer handed to the C++ host).
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("lane.submit", "lane.collect", "lane.idle", "lane.gate",
              "lane.respond")


def short_name(name: str) -> str:
    """``%fusion.107 = s32[4096]{...} fusion(...)`` -> ``fusion.107
    s32[4096]``: the op and the first shape of its result."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = re.search(r"[a-z]+\d*\[[\d,]+\]", rest)
    return head.lstrip("%") + (" " + shape.group(0) if shape else "")


def load(trace_dir: str) -> dict:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: dict = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = [(short_name(e.name), int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            if evs:
                lines[line.name] = lines.get(line.name, []) + evs
        if lines:
            out[plane.name] = lines
    return out


def _device_planes(tr: dict) -> list[str]:
    return sorted(p for p in tr if p.startswith("/device:")
                  and "XLA Ops" in tr[p])


def _host_events(tr: dict, names) -> list[tuple]:
    out = []
    for plane, lines in tr.items():
        if plane.startswith("/device:"):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e[0] in names)
    return out


def window_of(tr: dict) -> tuple[int, int]:
    spans = _host_events(tr, (WINDOW_SPAN,))
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    name, start, dur = max(spans, key=lambda e: e[2])
    return start, start + dur


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [start, end) rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:].tolist():
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def _clip(evs, lo: int, hi: int) -> np.ndarray:
    iv = np.asarray([(s, s + d) for _, s, d in evs], np.int64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _is_module(name: str, module: str) -> bool:
    return name == module or name.startswith(module + "(")


def reduce(tr: dict, step_module: str = None) -> dict:
    lo, hi = window_of(tr)
    planes = _device_planes(tr)
    if not planes:
        raise ValueError("no device plane with XLA Ops in the trace")
    busy, gaps = [], []
    op_time: dict[str, float] = {}
    step_ns = []
    for p in planes:
        ops = tr[p]["XLA Ops"]
        u = _union(_clip(ops, lo, hi))
        busy.append(int((u[:, 1] - u[:, 0]).sum()) if len(u) else 0)
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        gaps.extend((s, e) for s, e in edges.tolist() if e > s)
        top_end = None
        for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
            if top_end is not None and s < top_end:
                continue            # runs inside a loop op counted whole
            top_end = s + d
            c = min(s + d, hi) - max(s, lo)
            if c > 0:
                op_time[name] = op_time.get(name, 0.0) + c / 1e9
        if step_module:
            step_ns.extend(d for name, s, d in tr[p].get("XLA Modules", ())
                           if _is_module(name, step_module)
                           and lo <= s and s + d <= hi)
    spans = sorted(_host_events(tr, HOST_SPANS), key=lambda e: e[1])
    starts = np.asarray([s for _, s, _ in spans], np.int64)
    idle: dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        label = "no_span"
        k = int(np.searchsorted(starts, mid, side="right")) - 1
        # the latest-starting span that covers the midpoint is the
        # innermost (a collect nests in the pump's gate); look back a few
        # for one that still covers it
        for j in range(k, max(-1, k - 8), -1):
            name, st, d = spans[j]
            if st <= mid < st + d:
                label = name
                break
        idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": float(np.mean(busy)) / 1e9,
        "devices": len(planes),
        "step_launches": len(step_ns),
        "step_device_s": float(sum(step_ns)) / 1e9,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }
