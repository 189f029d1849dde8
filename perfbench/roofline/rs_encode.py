"""Operations and bytes of one execution of the RS(10,4) encode step,
from the algorithm's shapes (not from XLA's cost estimate).

One step takes `batch_units` units of (10 data rows x 1 MiB) split over
`devices` chips and produces 4 parity rows per unit: every data byte is
read once and every parity byte written once, and each parity byte is
the GF(2^8) sum of 10 products (a multiply and an add each).  The shard
CRCs the fused step also produces read the same bytes and are not
counted again."""

DATA_ROWS = 10
PARITY_ROWS = 4
UNIT_ROW_BYTES = 1 << 20


def work(units_per_chip: float) -> dict:
    row_bytes = units_per_chip * UNIT_ROW_BYTES
    return {"bytes": (DATA_ROWS + PARITY_ROWS) * row_bytes,
            "int_ops": 2 * DATA_ROWS * PARITY_ROWS * row_bytes}


def work_per_event(ctx: dict) -> dict | None:
    seals = (ctx.get("records") or {}).get("seal")
    if not seals:
        return None
    stats = seals[-1]["stage_stats"]
    if not stats.get("batch_units") or not stats.get("devices"):
        return None
    return work(stats["batch_units"] / stats["devices"])
