"""Device time of the executor program (``kernels/batch_executor``
``fused_window``) per op it executed: the trace's executions of that
program over the jit driver's ``device_ops``."""

LAYER = "device program"
UNIT = "us/op"
MOVES = "ops_per_s"


def read(record):
    summary = record["trace"]
    n = record["counts"].get("device_ops", 0)
    if summary is None or not n:
        return None
    secs = [s for name, s in summary.programs.items()
            if "fused_window" in name]
    if not secs:
        return None
    return sum(secs) / n * 1e6
