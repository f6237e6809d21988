"""The analyzer analyzed: every rule ID must FIRE on a known-bad
fixture and stay SILENT on the shipped tree (modulo the checked-in
baseline).  A rule that can't catch its own fixture is dead weight; a
rule that fires on shipped code is either a real regression (fix the
code) or a missing baseline entry (justify it) — either way CI blocks.
"""
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import Baseline, split_findings
from repro.analysis import concurrency_check as cc
from repro.analysis import hotpath_check as hc
from repro.analysis import kernel_check as kc

f32 = jnp.float32


def _sds(shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Pass 1 fixtures: one deliberately Mosaic-hostile kernel per KC rule
# ---------------------------------------------------------------------------


def test_kc000_fires_on_missing_recipe_and_dead_recipe():
    assert _rules(kc.check_coverage(["made_up_op"], kc.recipes())) == {"KC000"}
    # a recipe that never reaches a pallas_call is also KC000
    fs = kc.check_traced("fixture/plain", lambda x: x * 2, (_sds((8, 128)),))
    assert _rules(fs) == {"KC000"}


def test_kc001_fires_on_1d_iota():
    def kernel(x_ref, o_ref):
        idx = jax.lax.iota(jnp.int32, 128)
        o_ref[...] = x_ref[...] + idx.reshape(1, 128).astype(f32)

    def op(x):
        return pl.pallas_call(kernel, out_shape=_sds((8, 128)),
                              interpret=True)(x)

    assert "KC001" in _rules(kc.check_traced("fixture/iota", op,
                                             (_sds((8, 128)),)))


def test_kc002_fires_on_1d_intermediate_but_not_keepdims_reduce():
    def kernel(x_ref, o_ref):
        flat = x_ref[...].reshape(-1)            # (1024,) — no VREG layout
        o_ref[...] = flat.reshape(x_ref.shape)

    def op(x):
        return pl.pallas_call(kernel, out_shape=_sds((8, 128)),
                              interpret=True)(x)

    assert "KC002" in _rules(kc.check_traced("fixture/vec", op,
                                             (_sds((8, 128)),)))

    def ok_kernel(x_ref, o_ref):
        m = x_ref[...].max(-1, keepdims=True)    # reduce+reshape pair is fine
        o_ref[...] = x_ref[...] - m

    def ok_op(x):
        return pl.pallas_call(ok_kernel, out_shape=_sds((8, 128)),
                              interpret=True)(x)

    assert kc.check_traced("fixture/keepdims", ok_op, (_sds((8, 128)),)) == []


def test_kc003_fires_on_lane_misaligned_block():
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def op(x):
        return pl.pallas_call(
            kernel, grid=(4,),
            in_specs=[pl.BlockSpec((8, 64), lambda i: (0, i))],
            out_specs=pl.BlockSpec((8, 64), lambda i: (0, i)),
            out_shape=_sds((8, 256)), interpret=True)(x)

    assert "KC003" in _rules(kc.check_traced("fixture/lane", op,
                                             (_sds((8, 256)),)))


def test_kc004_fires_on_sublane_misaligned_block():
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def op(x):
        return pl.pallas_call(
            kernel, grid=(4, 2),
            in_specs=[pl.BlockSpec((1, 12, 128), lambda i, j: (i, j, 0))],
            out_specs=pl.BlockSpec((1, 12, 128), lambda i, j: (i, j, 0)),
            out_shape=_sds((4, 24, 128)), interpret=True)(x)

    assert "KC004" in _rules(kc.check_traced("fixture/sublane", op,
                                             (_sds((4, 24, 128)),)))


def test_kc005_fires_on_bad_vmem_scratch():
    def kernel(x_ref, o_ref, v1, vlane, vtiny):
        v1[...] = x_ref[0]                       # 1-D VMEM
        vlane[...] = x_ref[...][:, :64]          # minor 64, not 128
        vtiny[0, 0] = x_ref[0, 0]                # size-1 VMEM -> SMEM
        o_ref[...] = x_ref[...] * 2

    def op(x):
        return pl.pallas_call(
            kernel, out_shape=_sds((8, 128)),
            scratch_shapes=[pltpu.VMEM((128,), f32),
                            pltpu.VMEM((8, 64), f32),
                            pltpu.VMEM((1, 1), f32)],
            interpret=True)(x)

    fs = [f for f in kc.check_traced("fixture/scratch", op, (_sds((8, 128)),))
          if f.rule == "KC005"]
    assert len(fs) == 3


def test_kc005_skips_semaphores_but_sizes_vmem_beside_them():
    def kernel(x_ref, o_ref, dma, sem, vlane):
        vlane[...] = x_ref[...][:, :64]          # minor 64, not 128
        o_ref[...] = x_ref[...] * 2

    def op(x):
        return pl.pallas_call(
            kernel, out_shape=_sds((8, 128)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SemaphoreType.REGULAR(()),
                            pltpu.VMEM((8, 64), f32)],
            interpret=True)(x)

    fs = [f for f in kc.check_traced("fixture/sems", op, (_sds((8, 128)),))
          if f.obj.startswith("scratch")]
    assert [(f.rule, f.obj.split(":")[0]) for f in fs] == [
        ("KC005", "scratch[2]")]


def test_kc006_fires_on_float_prefetch_and_oversized_smem():
    def kernel(p_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...] + p_ref[0]

    def op(p, x):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i, pr: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i, pr: (0, 0)))
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              out_shape=_sds((8, 128)), interpret=True)(p, x)

    assert "KC006" in _rules(kc.check_traced(
        "fixture/prefetch", op, (_sds((4,), f32), _sds((8, 128)))))

    def big_kernel(x_ref, o_ref, s_ref):
        s_ref[0, 0] = jnp.int32(0)
        o_ref[...] = x_ref[...]

    def big_op(x):
        return pl.pallas_call(
            big_kernel, out_shape=_sds((8, 128)),
            scratch_shapes=[pltpu.SMEM((64, 64), jnp.int32)],
            interpret=True)(x)

    assert "KC006" in _rules(kc.check_traced("fixture/smem", big_op,
                                             (_sds((8, 128)),)))


def test_kc007_fires_on_non_affine_index_map():
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def op(x):
        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i ** 3, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=_sds((16, 128)), interpret=True)(x)

    assert "KC007" in _rules(kc.check_traced("fixture/idxmap", op,
                                             (_sds((16, 128)),)))


def test_kc008_fires_on_unlowerable_op():
    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.sort(x_ref[...], axis=-1)

    def op(x):
        return pl.pallas_call(kernel, out_shape=_sds((8, 128)),
                              interpret=True)(x)

    assert "KC008" in _rules(kc.check_traced("fixture/sort", op,
                                             (_sds((8, 128)),)))


# ---------------------------------------------------------------------------
# Pass 1 on the shipped tree: clean modulo the checked-in baseline
# ---------------------------------------------------------------------------


def test_kernel_pass_covers_every_kernel_spec_op():
    table = kc.recipes()
    expected = sorted(set(kc.public_ops()) | set(kc.kernel_spec_ops()))
    assert kc.check_coverage(expected, table) == []
    # every recipe names a real public op — no phantom coverage
    assert set(table) <= set(kc.public_ops())


def test_kernel_pass_shipped_tree_clean_modulo_baseline():
    findings = kc.run()
    baseline = Baseline.load()
    blocking, accepted = split_findings(findings, baseline)
    assert blocking == [], [f.fingerprint for f in blocking]
    # no stale entries: every baselined deviation still exists
    assert baseline.stale(findings) == []
    # the serve decode hot path must NOT hide behind the baseline
    hot = ("decode_view_attend", "flash_decode_paged", "mla_decode_views",
           "mla_decode_paged",
           "slot_gather", "slot_scatter", "sample_tokens")
    assert not [f for f in accepted
                if f.where.split("/")[0] in hot], accepted


# ---------------------------------------------------------------------------
# Pass 2 fixtures
# ---------------------------------------------------------------------------


def test_hp001_fires_on_callback_in_dispatch():
    def op(x):
        y = jax.pure_callback(lambda v: v, _sds((8, 8)), x)
        return y * 2

    assert "HP001" in _rules(hc.check_fn("fixture/cb", op, (_sds((8, 8)),)))


def test_hp002_fires_on_host_control_flow():
    def op(x):
        if x.sum() > 0:          # tracer __bool__ — host round-trip
            return x
        return -x

    assert "HP002" in _rules(hc.check_fn("fixture/if", op, (_sds((8, 8)),)))


def test_hp003_fires_on_missed_donation_and_undonatable_arg():
    big = _sds((256, 256))       # 256 KiB, over the large-buffer bar

    def op(cache, tok):
        return cache + 1.0, tok.sum()

    fs = hc.check_fn("fixture/nodonate", op, (big, _sds((8,), jnp.int32)))
    assert "HP003" in _rules(fs)
    # donating arg 0 silences the missed-alias direction
    assert "HP003" not in _rules(
        hc.check_fn("fixture/donated", op,
                    (big, _sds((8,), jnp.int32)), donate=(0,)))

    def drops(cache):
        return cache.sum()       # donated buffer never returned

    fs = hc.check_fn("fixture/undonatable", drops, (big,), donate=(0,))
    assert any(f.rule == "HP003" and "undonatable" in f.obj for f in fs)


def test_hp004_fires_on_baked_constant():
    table = jnp.ones((256, 256), f32)     # closure-captured device data

    def op(x):
        return x @ table

    assert "HP004" in _rules(hc.check_fn("fixture/const", op,
                                         (_sds((8, 256)),)))


def test_hp005_fires_on_weak_typed_leaf():
    def op(x, t):
        return x * t

    fs = hc.check_fn("fixture/weak", op, (_sds((8, 8)), 0.5))
    assert "HP005" in _rules(fs)
    # the same scalar as a concretely-dtyped struct is fine
    assert hc.check_fn("fixture/strong", op,
                       (_sds((8, 8)), _sds((), f32))) == []


def test_hotpath_shipped_dispatch_clean():
    # one block-pool family and one slot-state family; the full sweep
    # runs in CI via the CLI
    assert hc.check_arch("qwen1.5-0.5b") == []
    assert hc.check_arch("mamba2-370m") == []


# ---------------------------------------------------------------------------
# Pass 3 fixtures
# ---------------------------------------------------------------------------

_BAD_WORKER = textwrap.dedent("""
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self._count = 0

        def start(self):
            t = threading.Thread(target=self._run, daemon=True)
            t.start()

        def _run(self):
            self._items.append(1)        # SC001: unguarded write
            self._count += 1             # SC001: unguarded rebind

        def size(self):
            return len(self._items)      # SC002: unguarded read

        def items(self):
            with self._lock:
                return self._items       # SC003: live-container escape
""")


def _lint_source(tmp_path, src, name="fixture.py"):
    (tmp_path / name).write_text(src)
    return cc.run(root=str(tmp_path))


def test_sc_rules_fire_on_bad_worker(tmp_path):
    fs = _lint_source(tmp_path, _BAD_WORKER)
    assert _rules(fs) == {"SC001", "SC002", "SC003"}
    assert {f.obj for f in fs if f.rule == "SC001"} == {"_items", "_count"}


def test_sc_lock_discipline_and_private_fixpoint_pass(tmp_path):
    fs = _lint_source(tmp_path, textwrap.dedent("""
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self._items.append(1)
                    self._bump()

            def _bump(self):                 # called only under the lock
                self._items.append(2)

            def items(self):
                with self._lock:
                    return list(self._items)
    """))
    assert fs == []


def test_sc_single_writer_annotation_exempts_class(tmp_path):
    fs = _lint_source(tmp_path, _BAD_WORKER.replace(
        "class Worker:",
        "# analysis: single-writer — fixture claim\nclass Worker:"))
    assert fs == []


def test_sc_propagates_one_hop_to_constructed_helpers(tmp_path):
    fs = _lint_source(tmp_path, textwrap.dedent("""
        import threading

        class Book:
            def __init__(self):
                self.load = {}

            def charge(self, k):
                self.load[k] = self.load.get(k, 0) + 1   # SC001

        class Front:
            def __init__(self):
                self.book = Book()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self.book.charge(0)
    """))
    # the write trips SC001 and the read-modify half trips SC002 — both
    # on the helper one hop out
    assert {(f.rule, f.obj) for f in fs} == {("SC001", "load"),
                                            ("SC002", "load")}
    assert all("Book.charge" in f.where for f in fs)


def test_sc_init_param_annotations_pull_injected_helpers(tmp_path):
    """A helper the worker-root RECEIVES (rather than constructs) is
    still shared state: the ``Optional["Plan"]`` string annotation on
    ``__init__`` must pull Plan into the shared set so its unguarded
    mutation is flagged — the dispatcher's injected FaultPlan is
    exactly this shape."""
    fs = _lint_source(tmp_path, textwrap.dedent("""
        import threading
        from typing import Optional

        class Plan:
            def __init__(self):
                self.fired = []

            def mark(self, k):
                self.fired.append(k)         # SC001: no lock

        class Front:
            def __init__(self, plan: Optional["Plan"] = None):
                self.plan = plan

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                if self.plan is not None:
                    self.plan.mark(0)
    """))
    assert ("SC001", "fired") in {(f.rule, f.obj) for f in fs}
    assert any("Plan.mark" in f.where for f in fs)


def test_sc_safe_stdlib_types_are_exempt_unless_rebound(tmp_path):
    fs = _lint_source(tmp_path, textwrap.dedent("""
        import queue
        import threading

        class Worker:
            def __init__(self):
                self._q = queue.Queue()
                self._stop = threading.Event()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self._q.put(1)               # internally locked: fine
                self._stop.set()

            def reset(self):
                self._q = queue.Queue()      # rebind: NOT fine
    """))
    # the rebind is SC001, and once the attr CAN be rebound every bare
    # read of it races too (the worker may see either queue) — SC002
    assert {(f.rule, f.obj) for f in fs} == {("SC001", "_q"),
                                            ("SC002", "_q")}


def test_concurrency_shipped_serve_tree_clean():
    assert cc.run() == []


# ---------------------------------------------------------------------------
# baseline mechanics + CLI
# ---------------------------------------------------------------------------


def test_baseline_split_and_stale_detection():
    from repro.analysis.common import Finding
    f1 = Finding("KC005", "some_op/default", "scratch[0]", "d", "x")
    f2 = Finding("KC001", "other_op/default", "iota(8,)", "d", "x")
    base = Baseline(entries={
        f1.fingerprint: {"fingerprint": f1.fingerprint, "reason": "r"},
        "KC009:gone/op:x": {"fingerprint": "KC009:gone/op:x"},
    })
    blocking, accepted = split_findings([f1, f2], base)
    assert blocking == [f2] and accepted == [f1]
    assert base.stale([f1, f2]) == ["KC009:gone/op:x"]


def test_cli_concurrency_pass_and_json_report(tmp_path):
    from repro.analysis.__main__ import main
    report = tmp_path / "report.json"
    rc = main(["--concurrency", "--json", str(report)])
    assert rc == 0
    doc = json.loads(report.read_text())
    assert doc["blocking_total"] == 0
    assert doc["passes"]["concurrency"] == {"blocking": [], "baselined": []}


def test_cli_exit_code_counts_blocking_findings(tmp_path, monkeypatch):
    from repro.analysis import __main__ as cli
    from repro.analysis.common import Finding
    bad = [Finding("SC001", "x.py:C.m", "attr", "d", "f")]
    monkeypatch.setattr(cc, "run", lambda root=None: bad)
    rc = cli.main(["--concurrency", "--baseline",
                   str(tmp_path / "empty.json")])
    assert rc == 1
