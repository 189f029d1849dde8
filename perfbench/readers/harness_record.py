"""Reader `harness_record`: replies the driver kept (the `stage_stats` of
every `/admin/ec/generate` of the window): the sum of one dotted key over
the sum of another, times `scale`."""


def _dig(record: dict, dotted: str):
    for part in dotted.split("."):
        if not isinstance(record, dict) or part not in record:
            return None
        record = record[part]
    return record


def read(spec: dict, ctx: dict) -> float | None:
    records = (ctx.get("records") or {}).get(spec["record"])
    if not records:
        return None
    num = [_dig(r, spec["key"]) for r in records]
    den = [_dig(r, spec["per"]) for r in records]
    if any(v is None for v in num + den) or not sum(den):
        return None
    return sum(num) / sum(den) * spec.get("scale", 1.0)
