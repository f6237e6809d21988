"""Model operations per token (bench/flops.py, remat not counted) times
the traced stretch's tokens, over its length times chips times peak."""
from bench import flops


def read(rec):
    if rec["kind"] != "train" or not rec.get("traced_s") or not rec["peaks"]:
        return None
    work = flops.train_flops_per_token(rec["dims"], rec["seq_len"]) \
        * rec["traced_tokens"]
    return 100.0 * work / (rec["traced_s"] * rec["chips"]
                           * rec["peaks"]["flops_bf16"])
