"""Reader `count`: a number the harness counted over the window, by
name (compile-cache entries added, bytes sealed)."""


def read(spec: dict, ctx: dict) -> float | None:
    value = (ctx.get("counts") or {}).get(spec["count"])
    return None if value is None else value * spec.get("scale", 1.0)
