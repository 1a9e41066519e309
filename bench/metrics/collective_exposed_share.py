"""collective_exposed_share: for each device, the time inside collective
ops (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, their ``-start``/``-done`` halves, and fusions that call
them) that no other op on that device covers, over the traced window,
averaged over the devices, in %. Nothing when no collective ran in the
window."""
from bench.lib import trace


def read(rec):
    if rec.get("trace") is None:
        return None
    share = trace.collective_exposed_share(rec["trace"])
    return None if share is None else 100.0 * share
