"""learner_mfu: model FLOPs of the window's learner steps (forward and
backward of every valid token, causal attention, no recompute) per
second of the window, over the chips' bf16 peak, in %."""
from bench.lib.work import train_flops


def read(rec):
    if rec["kind"] != "learn" or rec["window_s"] <= 0:
        return None
    flops = sum(train_flops(rec["config"], step) for step in rec["lengths"])
    return 100.0 * flops / rec["window_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops"])
