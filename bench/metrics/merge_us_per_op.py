"""Background merge per op: the benchmark's span around
``advance_merge`` (``core/dpm_pool.py`` ``merge_budget``)."""

LAYER = "merge plane"
UNIT = "us/op"
MOVES = "ops_per_s"


def read(record):
    if not record["ops"]:
        return None
    return record["merge_s"] / record["ops"] * 1e6
