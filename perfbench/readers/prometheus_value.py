"""Reader `prometheus_value`: the value of one sample of the volume
server's `/metrics` as the run's last scrape found it (a gauge the program
sets once, such as a start phase), times `scale`.  No scrape, or no sample
of that family with those labels, reads as nothing."""


def read(spec: dict, ctx: dict) -> float | None:
    scrapes = ctx.get("prom")
    if not scrapes:
        return None
    labels = spec.get("labels") or {}
    picked = [v for name, lab, v in scrapes[-1]
              if name == spec["family"]
              and all(lab.get(k) == v_ for k, v_ in labels.items())]
    return picked[0] * spec.get("scale", 1.0) if picked else None
