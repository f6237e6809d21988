"""Active decode rows over decode row-slots dispatched in the window
(engine counters decode_active_slot_steps / decode_slot_steps)."""


def read(rec):
    if rec["kind"] != "serve" or not rec["decode_slot_steps"]:
        return None
    return 100.0 * rec["decode_active_slot_steps"] / rec["decode_slot_steps"]
