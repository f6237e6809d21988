"""bench/program_trace.py on hand-built traces, and its probe at smoke
size on the CPU (no chip: the device numbers are left empty)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import bench_tiny  # noqa: E402
from bench import program_trace as pt  # noqa: E402

MS = 1_000_000
T0, T1 = "/host:CPU/1", "/host:CPU/2"

# a compiled executable's text: the fusion came from the attention's
# backward, the custom call is the named paged-attend kernel, the
# convert from the deferred update
HLO = """
HloModule jit_step
%fused_computation (param_0: f32[4]) -> f32[4] {
  ROOT %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(step)/lsgd.fwd_bwd/transpose(jvp())/attend/exp"}
}
ENTRY %main {
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/lsgd.fwd_bwd/transpose(jvp())/while/body/attend/exp" stack_frame_id=3}
  %flash_decode_paged.2 = bf16[8,1,12,128]{3,2,1,0} custom-call(%t), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attend/flash_decode_paged/pallas_call"}
  ROOT %convert.7 = f32[4]{0} convert(%fusion.3), metadata={op_name="jit(step)/lsgd.apply_pending/cond/branch_1_fun/convert_element_type"}
}
"""


def _trace():
    # window 0-100 ms.  Thread 1: step 0 over 0-40 (schedule 0-2,
    # dispatch 2-5, fetch 5-35 holding a nested span 10-12) and step 1
    # over 50-85 (fetch 60-70); thread 2: a route span 0-30.  Device:
    # the fusion 0-20, the kernel 20-50 and 60-65, the convert 70-80;
    # idle 50-60, 65-70, 80-100.
    prog = [("repro:engine.step", 0, 40 * MS, T0, {"step": 0}),
            ("repro:engine.schedule", 0, 2 * MS, T0, {}),
            ("repro:engine.dispatch", 2 * MS, 3 * MS, T0,
             {"phase": "mixed"}),
            ("repro:engine.fetch", 5 * MS, 30 * MS, T0, {"step": 0}),
            ("repro:engine.build", 10 * MS, 2 * MS, T0, {}),
            ("repro:engine.step", 50 * MS, 35 * MS, T0, {"step": 1}),
            ("repro:engine.fetch", 60 * MS, 10 * MS, T0, {"step": 0}),
            ("repro:dispatcher.route", 0, 30 * MS, T1, {"rid": 7})]
    return {"devices": {"/device:TPU:0": [
        ("%fusion.3 = f32[4]{0} fusion(%p)", 0, 20 * MS),
        ("%flash_decode_paged.2 = bf16[8,1,12,128]{3,2,1,0} "
         "custom-call(s32[8,4]{1,0} %t)", 20 * MS, 30 * MS),
        ("%flash_decode_paged.2 = bf16[8,1,12,128]{3,2,1,0} "
         "custom-call(s32[8,4]{1,0} %t)", 60 * MS, 5 * MS),
        ("convert.7", 70 * MS, 10 * MS)]},
        "host": [("bench:traced_window", 0, 100 * MS),
                 ("bench:engine_step", 45 * MS, 50 * MS)],
        "program": sorted(prog, key=lambda p: (p[1], -p[2]))}


def test_op_paths_from_hlo_text():
    paths = pt.op_paths_from_hlo(HLO)
    assert paths["fusion.3"].endswith("while/body/attend/exp")
    assert paths["flash_decode_paged.2"] == \
        "jit(step)/attend/flash_decode_paged/pallas_call"
    assert "lsgd.apply_pending" in paths["convert.7"]
    assert paths["exp.1"].endswith("attend/exp")    # inside the fusion


@pytest.mark.parametrize("path,scope,inside", [
    ("jit(step)/lsgd.fwd_bwd/transpose(jvp(attend))/dot", "attend", True),
    ("jit(step)/lsgd.fwd_bwd/jvp()/while/body/attend/exp", "attend", True),
    ("jit(step)/attend/decode_view_attend/pallas_call", "attend", True),
    ("jit(step)/paged_step/decode_view_attend/pallas_call", "attend",
     False),
    ("jit(step)/lsgd.fwd_bwd/mul", "lsgd.fwd", False),
    ("jit(step)/lsgd.apply_pending/cond/mul", "lsgd.apply_pending", True)])
def test_in_scope_matches_whole_components(path, scope, inside):
    assert pt.in_scope(path, scope) is inside


def test_scope_time_counts_ops_under_the_scope():
    t = _trace()
    paths = pt.op_paths_from_hlo(HLO)
    assert pt.scope_time(t, 0, 100 * MS, "attend", paths) == 55 * MS
    assert pt.scope_time(t, 0, 100 * MS, "lsgd.fwd_bwd", paths) == 20 * MS
    assert pt.scope_time(t, 0, 100 * MS, "lsgd.apply_pending",
                         paths) == 10 * MS
    # clipped to the window; ops with no known path count nowhere
    assert pt.scope_time(t, 10 * MS, 30 * MS, "attend", paths) == 20 * MS
    assert pt.scope_time(t, 0, 100 * MS, "attend", {}) == 0


def test_kernel_time_names_the_pallas_call():
    t = _trace()
    assert pt.kernel_of(t["devices"]["/device:TPU:0"][1][0]) \
        == "flash_decode_paged"
    assert pt.kernel_time(t, 0, 100 * MS, ["flash_decode_paged"]) \
        == (2, 35 * MS)
    # only executions wholly inside the window
    assert pt.kernel_time(t, 0, 55 * MS, ["flash_decode_paged"]) \
        == (1, 30 * MS)
    assert pt.kernel_time(t, 0, 100 * MS, pt.SAMPLERS) == (0, 0.0)


def test_span_self_time_subtracts_children():
    t = _trace()
    # all children: step 0 keeps 40 - (2 + 3 + 30) = 5, step 1 35 - 10
    assert pt.span_self_time(t, 0, 100 * MS, "repro:engine.step") \
        == (2, 15 * MS)
    # the fetch children only: the host's own time in the step
    assert pt.span_self_time(t, 0, 100 * MS, "repro:engine.step",
                             children=["repro:engine.fetch"]) \
        == (2, 17.5 * MS)
    # no children: the mean length; spans wholly inside the window only
    assert pt.span_self_time(t, 0, 45 * MS, "repro:engine.step",
                             children=()) == (1, 40 * MS)
    # another thread's spans are not children
    assert pt.span_self_time(t, 0, 100 * MS, "repro:dispatcher.route") \
        == (1, 30 * MS)


def test_idle_gaps_carry_the_innermost_span_of_either_kind():
    t = _trace()
    gaps = dict(pt.idle_gaps(t, 0, 100 * MS))
    assert gaps == {"repro:engine.step": pytest.approx(0.010),   # 50-60
                    "bench:engine_step": pytest.approx(0.020),   # 80-100
                    "repro:engine.fetch": pytest.approx(0.005)}  # 65-70


def test_spans_in_and_op_key():
    t = _trace()
    assert [p[4]["step"] for p in pt.spans_in(
        t, 0, 100 * MS, "repro:engine.fetch")] == [0, 0]
    assert pt.op_key("%fusion.3 = f32[4]{0} fusion(%p)") == "fusion.3"
    assert pt.op_key("convert.7") == "convert.7"


@pytest.mark.parametrize("cell", ["train-qwen1.5-0.5b-1chip",
                                  "serve-qwen2-1.5b-chat-overload"])
def test_probe_runs_at_smoke_size(cell):
    with bench_tiny.no_compile_cache():
        out = pt.probe(cell, 2**31 + 5, 4.0, require_tpu=False,
                       overrides=bench_tiny.overrides(cell))
    assert out["device"]["platform"] == "cpu"
    assert set(out["span_cost_ns"]) == {"off", "on_no_profiler",
                                        "on_profiled"}
    if cell.startswith("train"):
        assert out["metrics"]["loader_wait_ms.train"] >= 0
        assert out["metrics"]["attention_ms.train"] == 0   # no device ops
    else:
        n_steps, n_disp = out["engine_steps_dispatches"]
        assert n_steps > 0 and n_disp > 0
        assert out["metrics"]["engine_host_ms.overload"] > 0
        assert "repro:engine.fetch" in out["span_ms_mean"]
        assert set(out["tokens_per_s"]) == {"off", "spans_on",
                                            "spans_on_profiled"}
