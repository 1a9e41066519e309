"""logprob_kernel_roofline: the least time of the fused log-prob kernels'
calls in the window (each call's operand bytes over HBM bandwidth or its
arithmetic over the bf16 peak, whichever is larger), over the device
time of their events in the trace, in %. Nothing when the kernels are
not on the path."""
from bench.lib import trace
from bench.lib.work import logprob_kernel_bytes, logprob_kernel_flops

KERNELS = ("fused_logprob_fwd", "fused_logprob_bwd")


def read(rec):
    if rec["kind"] != "learn" or rec.get("trace") is None:
        return None
    spent = trace.op_seconds(rec["trace"], KERNELS)
    device_s = sum(spent.values())
    if device_s <= 0:
        return None
    calls = rec["steps"] * rec["grad_accum"]
    b = logprob_kernel_bytes(rec["logit_tokens"], rec["padded_vocab"])
    f = logprob_kernel_flops(rec["logit_tokens"], rec["padded_vocab"])
    pk = rec["peaks"]
    least = sum(max(b[k] / pk["hbm_bytes_per_s"], f[k] / pk["bf16_flops"])
                for k in ("fwd", "bwd"))
    return 100.0 * calls * least / device_s
