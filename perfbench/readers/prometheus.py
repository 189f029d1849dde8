"""Reader `prometheus`: a delta of the volume server's `/metrics` over
the window.

  stat "histogram_mean": delta of <family>_sum over delta of
       <family>_count, for the samples whose labels match.
  stat "delta_ratio": delta of <family> (labels match, `labels_not`
       do not) over the delta of `over.family` with `over.labels`, or
       over the harness count `over.count`.
Both are multiplied by `scale`.  No matching sample, or a denominator of
0, reads as nothing.
"""


def _total(samples, family, labels=None, labels_not=None) -> float | None:
    picked = [v for name, lab, v in samples
              if name == family
              and all(lab.get(k) == v_ for k, v_ in (labels or {}).items())
              and not any(lab.get(k) == v_
                          for k, v_ in (labels_not or {}).items())]
    return sum(picked) if picked else None


def _delta(ctx, family, labels=None, labels_not=None) -> float | None:
    before, after = ctx["prom"]
    b = _total(before, family, labels, labels_not)
    a = _total(after, family, labels, labels_not)
    return None if a is None else a - (b or 0.0)


def read(spec: dict, ctx: dict) -> float | None:
    if ctx.get("prom") is None:
        return None
    family, labels = spec["family"], spec.get("labels")
    if spec["stat"] == "histogram_mean":
        num = _delta(ctx, family + "_sum", labels)
        den = _delta(ctx, family + "_count", labels)
    elif spec["stat"] == "delta_ratio":
        num = _delta(ctx, family, labels, spec.get("labels_not"))
        over = spec["over"]
        den = (ctx["counts"].get(over["count"]) if "count" in over
               else _delta(ctx, over["family"], over.get("labels")))
    else:
        raise ValueError(f"prometheus reader: unknown stat {spec['stat']!r}")
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)
