"""Host<->device state bytes per op (``core/jit_engine.py``): the cache
state the jit driver packs and uploads for each KN per batch
(``jit.upload_bytes``) and pulls back on every scatter-back
(``jit.sync_bytes``), over ops.  An exact count; None where the store
keeps no such counter."""

LAYER = "jit driver"
UNIT = "B/op"
MOVES = "ops_per_s"


def read(record):
    counts = record["counts"]
    if not record["ops"] or "jit.upload_bytes" not in counts:
        return None
    return (counts["jit.upload_bytes"] + counts.get("jit.sync_bytes", 0)) \
        / record["ops"]
