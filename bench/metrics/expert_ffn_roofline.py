"""expert_ffn_roofline: the least time of the window's decode steps'
expert FFN work over the device time of the expert matmuls inside the
engine's decode-chunk programs, in %.

A decode step's least work is the larger of two figures: its bytes over
HBM bandwidth (the held experts its live tokens are expected to touch,
read once, and those tokens' rows in and out) and its FLOPs over the bf16
peak (6·d·f per expected held pair), from the model family's
``expert_ffn_work``. Live tokens per step come from the window's batches,
as ``decode_step_roofline`` takes them. The device time is that of the
grouped matmuls (XLA's ``ragged-dot`` kernels; the op that lays out their
groups, ``ragged-dot-metadata``, is not a matmul and is left out) that
run inside a ``_decode_chunk_jit`` program of the window; prefills are
left out. Nothing where the family has no expert FFN or the trace no
such op."""
import bisect

from bench.lib import spec

PROGRAM = "_decode_chunk_jit"
OPS = "%ragged-dot"
NOT_MATMUL = "metadata"


def decode_expert_seconds(tr) -> float:
    """Device seconds of the expert matmuls inside decode-chunk programs
    of the window, averaged over devices."""
    lo, hi = tr.window()
    total = 0.0
    for dev in tr.devices.values():
        progs = sorted((s, s + d) for n, s, d in dev.modules
                       if PROGRAM in n and s >= lo and s + d <= hi)
        starts = [s for s, _ in progs]
        for n, s, d in dev.ops:
            if not n.startswith(OPS) or NOT_MATMUL in n:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= progs[i][1]:
                total += d
    return total * 1e-9 / max(len(tr.devices), 1)


def least_seconds(c, batches, peak_flops: float, peak_bw: float) -> float:
    """Least time of the expert FFN over every decode step of the closed
    batches ``(prompt lengths, generated lengths)``."""
    work = spec.family(c).expert_ffn_work
    total = 0.0
    for _, gens in batches:
        for s in range(max(gens, default=0)):
            byts, flops = work(c, sum(1 for g in gens if g > s))
            total += max(byts / peak_bw, flops / peak_flops)
    return total


def read(rec):
    if (rec["kind"] != "rollout" or rec.get("trace") is None
            or not hasattr(spec.family(rec["config"]), "expert_ffn_work")):
        return None
    device_s = decode_expert_seconds(rec["trace"])
    if device_s <= 0:
        return None
    pk = rec["peaks"]
    least = least_seconds(rec["config"], rec["batches"], pk["bf16_flops"],
                          pk["hbm_bytes_per_s"])
    return 100.0 * least / device_s
