"""Reduction of a profiler trace (``.xplane.pb``) to busy, idle and
kernel times.

The window is the host span ``bench.window`` that the driver writes
(``harness.Window``).  On each device plane (``/device:TPU:<n>``):

* busy time is the union of the intervals of the device's operations
  (the ``XLA Ops`` line) inside the window;
* a kernel's time is the summed duration of the operations and programs
  whose name contains the kernel's pattern (both ``XLA Ops`` and ``XLA
  Modules`` are searched; the line with more time in the window wins, so
  a program is counted once).  A kernel that no event matches is an
  error, never a zero;
* idle gaps between busy intervals are attributed to what the host was
  doing at the gap's midpoint: the innermost host event that covers it.

``reduce_events`` works on plain event lists, so it can be tested on a
small recorded trace or on hand-built events.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SHORT_GAP_NS = 1e6


class TraceError(RuntimeError):
    pass


def union(iv: np.ndarray) -> np.ndarray:
    """Merge [start, end) intervals (n x 2) into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by disjoint intervals ``merged``."""
    c = clip(merged, lo, hi)
    return float((c[:, 1] - c[:, 0]).sum())


def load_events(path: str):
    """{"devices": {id: {line: [(name, start_ns, end_ns)]}},
    "host": [(name, start_ns, end_ns)]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns)
                                        for e in line.events]
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def short_name(name: str) -> str:
    """An XLA op's name without its HLO text (``%while.266 = (...)``
    -> ``while.266``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _iv(events):
    return np.asarray([(s, e) for _, s, e in events], np.float64
                      ).reshape(-1, 2)


def reduce_events(ev: dict, kernels: dict, span_names=("bench.run_epoch",)):
    """The reduction (module docstring).  Times in the result are in
    seconds."""
    wins = [(s, e) for n, s, e in ev["host"] if n == WINDOW_SPAN]
    if not wins:
        raise TraceError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = wins[0]
    if not ev["devices"]:
        raise TraceError("no TPU device plane in the trace")
    busy, ksum = {}, defaultdict(dict)
    ops_time = defaultdict(float)
    merged0 = None
    for dev, lines in sorted(ev["devices"].items()):
        ops = lines.get(OPS_LINE, [])
        merged = union(clip(_iv(ops), lo, hi))
        busy[dev] = float((merged[:, 1] - merged[:, 0]).sum())
        if merged0 is None:
            merged0 = merged
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops_time[short_name(name)] += d / len(ev["devices"])
        for label, pattern in kernels.items():
            best = 0.0
            for ln in (OPS_LINE, MODULES_LINE):
                t = sum(max(min(e, hi) - max(s, lo), 0.0)
                        for n, s, e in lines.get(ln, []) if pattern in n)
                best = max(best, t)
            ksum[label][dev] = best
    for label, per in ksum.items():
        if not any(v > 0 for v in per.values()):
            raise TraceError(f"no device event matches kernel {label!r} "
                             f"(pattern {kernels[label]!r})")
    window = hi - lo
    # host spans of the driver, each with the device-0 busy time inside
    spans = defaultdict(list)
    for n, s, e in ev["host"]:
        if n in span_names and s >= lo and e <= hi:
            spans[n].append(((e - s) * 1e-9, covered(merged0, s, e) * 1e-9))
    # idle gaps on device 0, by the innermost host event at their
    # middle; gaps under SHORT_GAP_NS (between the ops of one program)
    # are summed as one entry
    gaps = defaultdict(float)
    edges = np.concatenate([[lo], merged0.ravel(), [hi]]).reshape(-1, 2)
    host = [(n, s, e) for n, s, e in ev["host"] if n != WINDOW_SPAN]
    hn = np.asarray([n for n, _, _ in host], object)
    hs = np.asarray([s for _, s, _ in host], np.float64)
    he = np.asarray([e for _, _, e in host], np.float64)
    for s, e in edges:
        if e <= s:
            continue
        if e - s < SHORT_GAP_NS:
            gaps["(gaps under 1 ms)"] += (e - s) * 1e-9
            continue
        mid = 0.5 * (s + e)
        cover = np.nonzero((hs <= mid) & (mid < he))[0]
        name = (hn[cover[np.argmin(he[cover] - hs[cover])]]
                if cover.size else "(host idle)")
        gaps[name] += (e - s) * 1e-9
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window * 1e-9,
        "busy_s": float(np.mean(list(busy.values()))) * 1e-9,
        "busy_by_device_s": {d: b * 1e-9 for d, b in busy.items()},
        "kernel_sum_s": {k: sum(v.values()) * 1e-9 for k, v in ksum.items()},
        "kernel_max_s": {k: max(v.values()) * 1e-9 for k, v in ksum.items()},
        "device_ops": [[n, t * 1e-9] for n, t in top],
        "idle_gaps": [[n, t] for n, t in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        "spans": dict(spans),
    }


def reduce_dir(path: str, kernels: dict, platform: str = "tpu"):
    """Reduce the one trace the profiler wrote under ``path``; None off
    the chip (a CPU trace has no device plane to reduce)."""
    if platform != "tpu":
        return None
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise TraceError(f"expected one xplane file under {path}, "
                         f"found {len(files)}")
    ev = load_events(files[0])
    try:
        return reduce_events(ev, kernels)
    except TraceError:
        describe(ev)
        raise


def describe(ev: dict, top: int = 8) -> None:
    """Print the busiest event names of each device line and the host's
    span names to standard error (what a kernel pattern can match)."""
    import sys
    for dev, lines in sorted(ev["devices"].items()):
        for ln, events in lines.items():
            tot = defaultdict(float)
            for n, s, e in events:
                tot[n] += (e - s) * 1e-9
            best = [(n[:300], t) for n, t in
                    sorted(tot.items(), key=lambda kv: -kv[1])[:top]]
            print(f"trace: device {dev} {ln}: {best}", file=sys.stderr)
    names = sorted({n for n, _, _ in ev["host"]})[:50]
    print(f"trace: host event names {names}", file=sys.stderr)
