"""Where the benchmark meets the system under test (``src/repro``).

A configuration file names its model family (``"family": "<name>"``);
``family`` returns ``bench/reference/<name>.py``, which holds all that
depends on the family: the published sizes, the seeded weights in the
benchmark's own layout, the mapping to and from the program's parameter
tree, the float32 reference and the operation counts.  The benchmark
layout is one flat dict, leaf name -> array, whatever names the family
gives its leaves.  Everything here holds for every family.
"""
from __future__ import annotations

import importlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# the names every family module exports; its docstring says what each is
INTERFACE = ("PUBLISHED", "check_published", "dims",
             "shapes", "generate", "make_weights",
             "to_program", "from_program",
             "train_steps", "leaf_norms", "logits_at",
             "train_flops_per_token", "serve_flops", "attend_cost")


def import_program():
    """Put the checkout's ``src`` on the path; fails (ImportError) in a
    directory that holds only the benchmark."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def family(spec: dict):
    """The family module that ``spec`` names under ``"family"``:
    a configuration file, or a cell's record, which carries it on."""
    name = spec.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise SystemExit(f"{spec.get('name', 'record')}: no model family "
                         f"(\"family\": <module in bench/reference/>), "
                         f"got {name!r}")
    mod_name = f"bench.reference.{name}"
    try:
        mod = importlib.import_module(mod_name)
    except ModuleNotFoundError as e:
        if e.name != mod_name:
            raise
        raise SystemExit(f"family {name!r}: no bench/reference/{name}.py")
    missing = [k for k in INTERFACE if not hasattr(mod, k)]
    if missing:
        raise SystemExit(f"family {name!r}: {mod_name} lacks {missing}")
    return mod


def model_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file; its family
    refuses one whose sizes differ from the published numbers the file
    holds."""
    from repro.configs.base import get_config
    fam = family(cfg_file)
    mc = get_config(cfg_file["registry_name"])
    mc = mc.replace(**cfg_file.get("program_overrides", {}))
    if cfg_file.get("check_published", True):
        fam.check_published(cfg_file, mc)
    return mc


def check_layout(model, params) -> None:
    """Raise if the program's parameter tree is not the one the family's
    ``to_program`` builds (a refactor of the model must fail loudly, not
    misplace)."""
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
