"""From a profiler trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData` and nothing else.  A device plane is
`/device:TPU:<n>`; its `XLA Ops` line carries one event per operation that
ran on the chip and its `XLA Modules` line one per executed program
(`jit_<function>(<id>)`).  Times in the file are nanoseconds from the
start of the trace.

  busy_s    union of the XLA Ops intervals, averaged over the chips that
            ran anything
  modules   every program execution: (chip, name, start_s, seconds)
  device_ops  the operations that took most device time, summed over chips
  idle_gaps   the device's idle gaps on the busiest chip, summed by what
              the client had in flight (the harness's own spans) and the
              traced host event that covers most of the gap
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MAX_GAPS = 2000


def find_xplane(logdir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged (start, end) rows of possibly overlapping intervals."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.ones(len(iv), dtype=bool)
    first[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[first, 0]
    last = np.append(np.flatnonzero(first)[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def reduce_trace(path: str, window_s: float | None = None,
                 client_spans: list[tuple[str, float, float]] = ()) -> dict:
    """`client_spans` are (name, start_s, end_s) on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: dict[int, np.ndarray] = {}
    op_seconds: dict[str, float] = {}
    modules = []
    host = []
    extent = [float("inf"), 0.0]
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv = []
                    for e in line.events:
                        iv.append((e.start_ns, e.start_ns + e.duration_ns))
                        op_seconds[e.name] = op_seconds.get(
                            e.name, 0.0) + e.duration_ns / 1e9
                    if iv:
                        chips[chip] = _union(np.array(iv, dtype=np.float64))
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        modules.append((chip, e.name, e.start_ns / 1e9,
                                        e.duration_ns / 1e9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
    for chip, iv in chips.items():
        extent = [min(extent[0], iv[0, 0]), max(extent[1], iv[-1, 1])]
    for s, e, _ in host:
        extent = [min(extent[0], s), max(extent[1], e)]
    busy = {chip: float((iv[:, 1] - iv[:, 0]).sum()) / 1e9
            for chip, iv in chips.items()}
    if window_s is None:
        window_s = max(0.0, extent[1] - extent[0]) / 1e9 if host or chips \
            else 0.0
    out = {
        "window_s": window_s,
        "chips": sorted(chips),
        "busy_by_chip": busy,
        "busy_s": sum(busy.values()) / len(busy) if busy else 0.0,
        "modules": modules,
        "device_ops": sorted(([name[:120], sec]
                              for name, sec in op_seconds.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": [],
    }
    if chips:
        busiest = max(busy, key=busy.get)
        out["idle_gaps"] = _idle_gaps(chips[busiest], window_s * 1e9, host,
                                      client_spans)
    return out


def _idle_gaps(busy_iv: np.ndarray, window_ns: float, host: list,
               client_spans) -> list:
    edges = np.concatenate([[0.0], busy_iv.reshape(-1),
                            [max(window_ns, busy_iv[-1, 1])]])
    gaps = edges.reshape(-1, 2)
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    order = np.argsort(gaps[:, 0] - gaps[:, 1])
    named: dict[str, float] = {}
    if len(order) > MAX_GAPS:
        rest = gaps[order[MAX_GAPS:]]
        named["gaps too short to attribute"] = float(
            (rest[:, 1] - rest[:, 0]).sum()) / 1e9
        order = order[:MAX_GAPS]
    hs = np.array([h[0] for h in host], dtype=np.float64)
    he = np.array([h[1] for h in host], dtype=np.float64)
    span_names = sorted({n for n, _, _ in client_spans})
    span_kind = np.array([span_names.index(n) for n, _, _ in client_spans],
                         dtype=np.int64)
    ss = np.array([s for _, s, _ in client_spans], dtype=np.float64) * 1e9
    se = np.array([e for _, _, e in client_spans], dtype=np.float64) * 1e9
    for g0, g1 in gaps[order]:
        mid = (g0 + g1) / 2
        kinds = [span_names[k] for k in np.unique(
            span_kind[(ss <= mid) & (mid < se)])]
        label = "client: " + "+".join(kinds) if kinds else "client: nothing"
        if len(hs):
            # what the host did in the gap: the traced host event that
            # covers most of it, long-lived wrappers (a thread parked in
            # one TraceMe across many gaps) left out
            overlap = np.minimum(he, g1) - np.maximum(hs, g0)
            overlap[(he - hs) > 4 * (g1 - g0)] = 0
            pick = int(overlap.argmax())
            if overlap[pick] > 0:
                label += " | host: " + host[pick][2][:60]
            else:
                label += " | no traced host event"
        named[label] = named.get(label, 0.0) + float(g1 - g0) / 1e9
    return sorted(([k, v] for k, v in named.items()),
                  key=lambda x: -x[1])[:10]
