"""Share of ops the host window engine ran instead of the device:
the jit driver's ``host_ops.<reason>`` counts over all ops."""

LAYER = "host window engine"
UNIT = "%"
MOVES = "ops_per_s"


def read(record):
    if not record["ops"]:
        return None
    host = sum(v for k, v in record["counts"].items()
               if k.startswith("host_ops."))
    return host / record["ops"] * 100.0
