"""slot_occupancy: decode-slot steps that carried a live request over all
decode-slot steps of the window (the engine's own counters), in %."""


def read(rec):
    if rec["kind"] != "rollout" or rec["decode_steps"] <= 0:
        return None
    return 100.0 * rec["decode_slot_steps"] / (
        rec["decode_steps"] * rec["num_slots"])
