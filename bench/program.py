"""Where the benchmark meets the system under test (``src/repro``).

Nests the benchmark's seeded weights (``reference/weights.py``) into the
program's parameter tree and names the program's leaves back in the
benchmark's layout.  Nothing here computes: it only moves arrays.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_ATTN = ("wq", "bq", "wk", "bk", "wv", "bv", "wo")
_MLP = ("w_gate", "w_up", "w_down")


def import_program():
    """Put the checkout's ``src`` on the path; fails (ImportError) in a
    directory that holds only the benchmark."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


# published config.json key -> the program's ModelConfig field
PUBLISHED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "tie_word_embeddings": "tie_embeddings"}


def model_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file; refuses one
    whose sizes differ from the published numbers the file holds."""
    from repro.configs.base import get_config
    mc = get_config(cfg_file["registry_name"])
    mc = mc.replace(**cfg_file.get("program_overrides", {}))
    if cfg_file.get("check_published", True):
        bad = {k: (cfg_file[k], getattr(mc, f)) for k, f in PUBLISHED.items()
               if cfg_file[k] != getattr(mc, f)}
        if mc.head_dim * mc.num_heads != cfg_file["hidden_size"]:
            bad["head_dim"] = (cfg_file["hidden_size"], mc.head_dim)
        if bad:
            raise SystemExit(f"{mc.name}: differs from its source: {bad}")
    return mc


def dims_of(mc) -> dict:
    return {"num_layers": mc.num_layers, "d_model": mc.d_model,
            "num_heads": mc.num_heads, "num_kv_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim, "d_ff": mc.d_ff,
            "vocab_size": mc.vocab_size, "rope_theta": mc.rope_theta,
            "norm_eps": mc.norm_eps}


def to_program(w: dict) -> dict:
    """Benchmark layout -> the program's dense-decoder parameter tree."""
    return {"embed": {"embedding": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "layers": {"run_0": {
                "ln1": {"scale": w["ln1"]},
                "attn": {k: w[k] for k in _ATTN},
                "ln2": {"scale": w["ln2"]},
                "mlp": {k: w[k] for k in _MLP}}}}


def from_program(p: dict) -> dict:
    """The program's parameter-shaped tree -> benchmark layout."""
    run = p["layers"]["run_0"]
    out = {"embed": p["embed"]["embedding"],
           "final_norm": p["final_norm"]["scale"],
           "ln1": run["ln1"]["scale"], "ln2": run["ln2"]["scale"]}
    out.update({k: run["attn"][k] for k in _ATTN})
    out.update({k: run["mlp"][k] for k in _MLP})
    return out


def check_layout(model, params) -> None:
    """Raise if the program's parameter tree is not the one ``to_program``
    builds (a refactor of the model must fail loudly, not misplace)."""
    import jax
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(lambda p: p, params)
    ws = jax.tree_util.tree_structure(want)
    gs = jax.tree_util.tree_structure(got)
    if ws != gs:
        raise RuntimeError(f"program parameter tree changed:\n{ws}\n{gs}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise RuntimeError(f"leaf {a} vs {b}")
