"""Jit driver residency per op (``core/jit_engine.py``): packing and
uploading each KN's cache state per batch (``JitEngine.upload_s``) and
scattering it back (``ENGINE_WALL["jit_sync"]``)."""

LAYER = "jit driver"
UNIT = "us/op"
MOVES = "ops_per_s"


def read(record):
    if not record["ops"]:
        return None
    return (record["upload_s"] + record["engine_wall"]["jit_sync"]) \
        / record["ops"] * 1e6
