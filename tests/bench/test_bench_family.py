"""All that depends on the model family sits in one module,
``bench/reference/<family>.py``, which the configuration names: each
family exports the whole interface, no other benchmark module names a
family or one of its leaves, and a family laid out differently runs
through the unchanged train and serve cells."""
import ast
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench import program  # noqa: E402

CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((ROOT / "bench" / "configs").glob("*.json"))}
FAMILIES = sorted({c.get("family") for c in CONFIGS.values()} - {None})
TRAIN = "train-qwen1.5-0.5b-1chip"
SERVE = "serve-qwen2-1.5b-chat-overload"


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_configuration_names_its_family(name):
    cfg = CONFIGS[name]
    assert program.family(cfg).__name__ == f"bench.reference.{cfg['family']}"
    program.import_program()
    assert program.model_config(cfg).name == cfg["registry_name"]


@pytest.mark.parametrize("spec,says", [
    ({"name": "c"}, "no model family"),
    ({"name": "c", "family": "no_such_family"}, "no bench/reference/"),
    ({"name": "c", "family": "../qwen"}, "no model family")])
def test_family_refused_plainly(spec, says):
    with pytest.raises(SystemExit, match=re.escape(says)):
        program.family(spec)


def _small(name):
    """A configuration of family ``name`` at smoke size, and its model."""
    program.import_program()
    from repro.configs.base import get_config, smoke_variant
    from repro.models.model import build_model
    cfg = next(c for c in CONFIGS.values() if c.get("family") == name)
    mc = smoke_variant(get_config(cfg["registry_name"]))
    return mc, build_model(mc)


@pytest.mark.parametrize("name", FAMILIES)
def test_family_interface(name):
    fam = program.family({"family": name})
    for k in program.INTERFACE:
        assert hasattr(fam, k), k
    assert isinstance(fam.PUBLISHED, dict) and fam.PUBLISHED
    mc, model = _small(name)
    dims = fam.dims(mc)
    w = fam.make_weights(dims, 2**31 + 5, "float32")
    assert {k: v.shape for k, v in w.items()} == \
        {k: tuple(s) for k, s in fam.shapes(dims).items()}
    p = fam.to_program(w)
    program.check_layout(model, p)
    back = fam.from_program(p)
    assert sorted(back) == sorted(w)
    for k in w:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(w[k]),
                                      err_msg=k)


def test_only_the_family_module_knows_the_family():
    files, leaves = set(), set()
    for name in FAMILIES:
        fam = program.family({"family": name})
        files.add(Path(fam.__file__).resolve())
        leaves |= set(fam.shapes(fam.dims(_small(name)[0])))
    said = re.compile(r"(?i)\b(" + "|".join(map(re.escape, FAMILIES)) + ")")
    bad = []
    for path in sorted((ROOT / "bench").rglob("*.py")):
        if path.resolve() in files:
            continue
        src = path.read_text()
        rel = path.relative_to(ROOT)
        if said.search(src):
            bad.append(f"{rel}: names a family")
        for node in ast.walk(ast.parse(src)):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [f"{node.module}.{a.name}" for a in node.names]
            for m in mods:
                if m.startswith("bench.reference.") \
                        and m != "bench.reference.common" \
                        and not m.startswith("bench.reference.common."):
                    bad.append(f"{rel}:{node.lineno}: imports {m}")
            word = (node.id if isinstance(node, ast.Name) else
                    node.attr if isinstance(node, ast.Attribute) else
                    node.arg if isinstance(node, (ast.arg, ast.keyword))
                    else node.value if isinstance(node, ast.Constant)
                    else None)
            if isinstance(word, str) and word in leaves:
                bad.append(f"{rel}:{node.lineno}: names leaf {word!r}")
    assert not bad, bad


@pytest.fixture
def stub_family(monkeypatch):
    import family_stub
    monkeypatch.setitem(sys.modules, "bench.reference.stub", family_stub)
    return family_stub


def _backlog_only(monkeypatch):
    """Serve the backlog alone, so that every request finishes inside the
    window and two runs of one seed serve the same tokens."""
    from bench import traffic
    schedule = traffic.schedule
    monkeypatch.setattr(traffic, "schedule", lambda mix, *a:
                        schedule(mix, *a)[:mix["backlog"]])
    return {"backlog": 3,
            "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_stub_family_runs_through_the_unchanged_cells(cell, stub_family,
                                                      monkeypatch):
    seed = 2**31 + 11
    kw = {"seconds": 1.0, "seed": seed}
    if cell == SERVE:
        kw = {"seconds": 8.0, "seed": seed,
              "traffic": _backlog_only(monkeypatch)}
    qwen = bench_tiny.run(cell, **kw)
    stub = bench_tiny.run(cell, config={"family": "stub"}, **kw)
    assert stub["correct"], stub["checks"]
    assert stub["checks"] == qwen["checks"]
    if cell == SERVE:
        assert stub["_info"]["finished"] == qwen["_info"]["finished"] == 3
        assert stub["_info"]["sample_served_tokens"] > 0
    else:
        assert stub["_info"]["losses_program"] == \
            qwen["_info"]["losses_program"]
        assert sorted(stub["_info"]["grad_norms_reference"]) == sorted(
            stub_family.PREFIX + k for k in
            qwen["_info"]["grad_norms_reference"])
