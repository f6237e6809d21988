"""Share of the traced stretch in which no operation ran on the device
(mean over chips).  Only on a chip the peaks table knows."""


def read(rec):
    if rec["kind"] != "train" or not rec.get("traced_s") \
            or not rec["peaks"]:
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["traced_s"])
