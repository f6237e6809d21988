"""Model operations per token (the family's ``train_flops_per_token``,
remat not counted) times the traced stretch's tokens, over its length
times chips times peak."""
from bench import program


def read(rec):
    if rec["kind"] != "train" or not rec.get("traced_s") or not rec["peaks"]:
        return None
    work = program.family(rec).train_flops_per_token(
        rec["dims"], rec["seq_len"]) * rec["traced_tokens"]
    return 100.0 * work / (rec["traced_s"] * rec["chips"]
                           * rec["peaks"]["flops_bf16"])
