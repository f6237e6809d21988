"""Model operations of the dispatches in the traced stretch (layers per
processed token, attention over live context, head per emitted token;
the family's ``serve_flops``, which ``serve_cell`` applies) over its
length times peak."""


def read(rec):
    if rec["kind"] != "serve" or not rec.get("traced_s") or not rec["peaks"]:
        return None
    return 100.0 * rec["traced_flops"] / (rec["traced_s"] * rec["chips"]
                                          * rec["peaks"]["flops_bf16"])
