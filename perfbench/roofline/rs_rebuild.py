"""Operations and bytes of one execution of the RS(10,4) rebuild step,
from the algorithm's shapes (not from XLA's cost estimate).

One step takes `batch_units` units of (10 survivor rows x `row_bytes`)
split over `devices` chips and produces one row per lost shard and unit:
every survivor byte is read once and every rebuilt byte written once, and
each rebuilt byte is the GF(2^8) sum of 10 products (a multiply and an add
each).  The CRCs of the rebuilt rows, which the step also produces, read
the same bytes and are not counted again.  The batch, the chips, the lost
shards and the row length are the last kept reply's (`stage_stats` of
`/admin/ec/rebuild`): a step always runs the full batch, its last one
padded with zero rows."""

SURVIVOR_ROWS = 10


def work(units_per_chip: float, lost_rows: int, row_bytes: int) -> dict:
    span = units_per_chip * row_bytes
    return {"bytes": (SURVIVOR_ROWS + lost_rows) * span,
            "int_ops": 2 * SURVIVOR_ROWS * lost_rows * span}


def work_per_event(ctx: dict) -> dict | None:
    rebuilds = (ctx.get("records") or {}).get("rebuild")
    if not rebuilds:
        return None
    stats = rebuilds[-1].get("stage_stats") or {}
    need = ("batch_units", "devices", "batches", "h2d_bytes", "missing")
    if not all(stats.get(k) for k in need):
        return None
    row_bytes = stats["h2d_bytes"] // (
        stats["batches"] * stats["batch_units"] * SURVIVOR_ROWS)
    return work(stats["batch_units"] / stats["devices"],
                len(stats["missing"]), row_bytes)
