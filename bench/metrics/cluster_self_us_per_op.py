"""Cluster self time per op: routing, write staging, probe prefetch and
result assembly in ``core/cluster.py`` -- the benchmark's span around
``execute_batch`` less the store's own engine clocks (``ENGINE_WALL``)
and the jit driver's upload time inside it."""

LAYER = "cluster"
UNIT = "us/op"
MOVES = "ops_per_s"


def read(record):
    if not record["ops"]:
        return None
    inner = sum(record["engine_wall"].values()) + record["upload_s"]
    return (record["execute_s"] - inner) / record["ops"] * 1e6
