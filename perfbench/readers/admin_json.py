"""Reader `admin_json`: the delta of one key of an admin endpoint's JSON
over the window, divided by the delta of another (`per`), times `scale`.
The harness fetches every `path` a layer metric names before and after
the window."""


def read(spec: dict, ctx: dict) -> float | None:
    pair = (ctx.get("admin") or {}).get(spec["path"])
    if pair is None:
        return None
    before, after = pair
    if spec["key"] not in after:
        return None
    num = after[spec["key"]] - before.get(spec["key"], 0)
    den = 1
    if "per" in spec:
        den = after.get(spec["per"], 0) - before.get(spec["per"], 0)
    if not den:
        return None
    return num / den * spec.get("scale", 1.0)
