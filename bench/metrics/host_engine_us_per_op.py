"""Host window engine per op (``core/transition.py``): the store's
``ENGINE_WALL`` clocks ``host_plan``, ``host_apply`` and
``host_replay`` over the window."""

LAYER = "host window engine"
UNIT = "us/op"
MOVES = "ops_per_s"


def read(record):
    if not record["ops"]:
        return None
    w = record["engine_wall"]
    return (w["host_plan"] + w["host_apply"] + w["host_replay"]) \
        / record["ops"] * 1e6
