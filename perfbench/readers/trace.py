"""Reader `trace`: device time of the program executions whose name, on
the trace's `XLA Modules` line, matches one of `patterns` (kept as data:
the kernels carry no stable names yet).

  stat "mean_us":      mean device microseconds of one execution.
  stat "roofline_pct": the least time the chip could take for the work
       of those executions over their device time.  The work of one
       execution comes from `roofline/<name>.py` (`work_per_event`),
       the peaks from `peaks.json` by `device_kind`; only the program
       with the most executions is read, since a rarer variant of it
       works on another shape.  `bound` in the log says which peak binds.
"""

import importlib
import re
from collections import Counter


def matching(spec: dict, ctx: dict) -> list[tuple]:
    trace = ctx.get("trace")
    if not trace:
        return []
    pats = [re.compile(p) for p in spec["patterns"]]
    return [m for m in trace["modules"] if any(p.search(m[1]) for p in pats)]


def read(spec: dict, ctx: dict) -> float | None:
    events = matching(spec, ctx)
    if not events:
        return None
    if spec["stat"] == "mean_us":
        return sum(e[3] for e in events) / len(events) * 1e6
    if spec["stat"] != "roofline_pct":
        raise ValueError(f"trace reader: unknown stat {spec['stat']!r}")
    program = Counter(e[1] for e in events).most_common(1)[0][0]
    events = [e for e in events if e[1] == program]
    work = importlib.import_module(
        "roofline." + spec["roofline"]).work_per_event(ctx)
    if work is None:
        return None
    peaks = ctx["peaks"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["int_ops"] / peaks["int8_ops_per_s"]
    ctx["log"](f"trace: {program}: {len(events)} executions, "
               f"{work['bytes']} bytes and {work['int_ops']} int ops each; "
               f"the {'bytes' if by_bytes >= by_ops else 'ops'} bound binds "
               f"({by_bytes * 1e6:.1f} us against {by_ops * 1e6:.1f} us)")
    return max(by_bytes, by_ops) * len(events) / sum(
        e[3] for e in events) * 100.0
