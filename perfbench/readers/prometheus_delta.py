"""Reader `prometheus_delta`: deltas over the window of two scrapes of any
daemon's `/metrics`, a sum of families over a sum of families.

  scrapes  where the two scrapes are: "prom" (the harness's own, of the
           volume server) or "records.<name>" (a driver's: each record
           holds `samples`, a list of (family, labels, value), as the
           driver of `s3-warp-mixed` keeps the gateway's)
  num      a list of {"family", "labels"}: their deltas are summed; a
           term with "or_zero" counts 0 where no sample matches (a
           labelled counter shows no sample until its first event)
  den      the same, or absent for the plain delta

`num / den x scale`.  A term with no matching sample in the window's last
scrape reads as nothing for the whole metric (a parent's program lacks
the family: the line leaves the metric out), and so does a denominator
of 0 or a scrape that is not there.
"""


def _scrapes(spec: dict, ctx: dict):
    where = spec["scrapes"]
    if where == "prom":
        return ctx.get("prom")
    records = (ctx.get("records") or {}).get(where.partition(".")[2])
    return records and [r.get("samples") or [] for r in records]


def _total(samples, term: dict) -> float | None:
    labels = term.get("labels") or {}
    picked = [v for name, lab, v in samples
              if name == term["family"]
              and all(lab.get(k) == v_ for k, v_ in labels.items())]
    return sum(picked) if picked else None


def _delta(scrapes, terms: list) -> float | None:
    total = 0.0
    for term in terms:
        after = _total(scrapes[-1], term)
        if after is None:
            if term.get("or_zero"):
                continue
            return None
        total += after - (_total(scrapes[0], term) or 0.0)
    return total


def read(spec: dict, ctx: dict) -> float | None:
    scrapes = _scrapes(spec, ctx)
    if not scrapes or len(scrapes) < 2:
        return None
    num = _delta(scrapes, spec["num"])
    den = _delta(scrapes, spec["den"]) if "den" in spec else 1.0
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)
