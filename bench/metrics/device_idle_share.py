"""Share of the traced window in which no program ran on the device."""

LAYER = "device"
UNIT = "%"
MOVES = "ops_per_s"


def read(record):
    summary = record["trace"]
    if summary is None:
        return None
    return summary.idle_share * 100.0
