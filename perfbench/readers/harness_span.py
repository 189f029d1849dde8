"""Reader `harness_span`: the harness's own spans of the window, by name:
`stat` "mean" or "median" of their seconds, times `scale`."""

import statistics


def read(spec: dict, ctx: dict) -> float | None:
    spans = (ctx.get("spans") or {}).get(spec["span"])
    if not spans:
        return None
    seconds = [end - start for start, end in spans]
    value = (statistics.median(seconds) if spec["stat"] == "median"
             else statistics.fmean(seconds))
    return value * spec.get("scale", 1.0)
