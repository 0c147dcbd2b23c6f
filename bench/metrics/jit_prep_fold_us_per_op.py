"""Jit driver host work around each dispatch per op: argument
preparation and the fold of the returned events into host bookkeeping
(``ENGINE_WALL`` ``jit_prep`` + ``jit_fold``)."""

LAYER = "jit driver"
UNIT = "us/op"
MOVES = "ops_per_s"


def read(record):
    if not record["ops"]:
        return None
    w = record["engine_wall"]
    return (w["jit_prep"] + w["jit_fold"]) / record["ops"] * 1e6
