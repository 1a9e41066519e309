"""device_idle_share.rollout: 1 - (union of device-busy intervals over
the traced window), averaged over the cell's devices, in %."""
from bench.lib import trace


def read(rec):
    if rec["kind"] != "rollout" or rec.get("trace") is None:
        return None
    return 100.0 * (1.0 - trace.busy_share(rec["trace"]))
