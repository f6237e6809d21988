"""flash_decode_paged's share of its roofline in the traced stretch: the
least time of one step (larger of operations over peak and needed bytes
over bandwidth: each row's query, each request's K/V up to its furthest
row, the outputs), averaged over the one-row-per-token steps dispatched
in the stretch, over the kernel's device time per step (its executions
wholly inside the stretch, one per layer)."""
from bench.reference.common import roofline_time


def read(rec):
    if rec["kind"] != "serve" or not rec.get("paged_costs") \
            or not rec.get("paged_executions") or not rec["peaks"]:
        return None
    least = sum(roofline_time(ops, nbytes, rec["peaks"])[0]
                for ops, nbytes in rec["paged_costs"]) / len(rec["paged_costs"])
    per_step = rec["paged_s"] * rec["dims"]["num_layers"] \
        / rec["paged_executions"]
    return 100.0 * least / per_step
