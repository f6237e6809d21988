"""Mean host time per window step spent waiting in next(HostLoader)."""


def read(rec):
    if rec["kind"] != "train" or not rec["steps"]:
        return None
    return 1e3 * rec["input_wait_s"] / rec["steps"]
