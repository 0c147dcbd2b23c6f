"""Share of KN cache lookups served from a cached value (the DAC,
``core/dac.py`` ``ArrayDAC``): ``aggregate_stats()["value_hit_ratio"]``
since the window began."""

LAYER = "KN cache"
UNIT = "%"
MOVES = "ops_per_s"


def read(record):
    stats = record["stats"]
    if not stats["ops"]:
        return None
    return stats["value_hit_ratio"] * 100.0
