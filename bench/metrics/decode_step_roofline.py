"""decode_step_roofline: the least time of the window's decode steps,
from the work the algorithm needs (weights read once a step, each live
request's own cached K/V, their FLOPs), over the device time of the
engine's decode-chunk programs in the trace, in %. It does not depend on
which paged-attention backend runs."""
from bench.lib import trace
from bench.lib.work import decode_least_seconds

PROGRAM = "_decode_chunk_jit"


def read(rec):
    if rec["kind"] != "rollout" or rec.get("trace") is None:
        return None
    device_s, _ = trace.module_seconds(rec["trace"], PROGRAM)
    if device_s <= 0:
        return None
    pk = rec["peaks"]
    least = sum(decode_least_seconds(rec["config"], p, g, pk["bf16_flops"],
                                     pk["hbm_bytes_per_s"])
                for p, g in rec["batches"])
    return 100.0 * least / device_s
