"""rollout_mfu: FLOPs of the generated tokens (2 per matmul weight plus
attention over each token's context) per second of the window, over the
chips' bf16 peak, in %."""
from bench.lib.work import attn_flops, matmul_params


def read(rec):
    if rec["kind"] != "rollout" or rec["window_s"] <= 0:
        return None
    c = rec["config"]
    n = matmul_params(c)
    flops = 0.0
    for plens, glens in rec["batches"]:
        for p, g in zip(plens, glens):
            # token j attends p + j + 1 keys
            flops += 2.0 * n * g + attn_flops(c, g * p + g * (g + 1) / 2.0)
    return 100.0 * flops / rec["window_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops"])
