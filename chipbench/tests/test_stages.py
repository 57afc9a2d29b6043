"""The stage attribution of ``stages.py``: made-up traces for each rule, a
few lines of real compiled text for the scope map, and a small trace
recorded on one TPU v5e chip by ``record_spans_trace.py``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import harness, stages  # noqa: E402

DATA = os.path.join(os.path.dirname(HERE), "testdata")
W = "bench.traced_window"

# lines of the split step's compiled text (two layers, CPU, cut 1)
HLO = r"""
  %frozen__embed__.1 = f32[512,256]{1,0} parameter(0), metadata={op_name="frozen[\'embed\']"}
  %constant.213 = f32[] constant(0)
  %add_any.176 = f32[32,512]{1,0} add(%param_1.3, %param_2.3), metadata={op_name="jit(split_grads)/transpose(jvp(sl.server_layers))/while/body/closed_call/add_any" stack_frame_id=272}
  %multiply.82 = f32[2,16]{1,0} multiply(%param_1.200, %broadcast.367), metadata={op_name="jit(split_grads)/sl.link/div" stack_frame_id=294}
  %bitcast.482 = f32[2,16,512]{2,1,0} bitcast(%param_5.257), metadata={op_name="jit(split_grads)/jvp(sl.head)/dot_general" stack_frame_id=283}
  ROOT %reduce_sum.209 = f32[2,16]{1,0} reduce(%param_0.643, %param_1.645), dimensions={2}, to_apply=%region_41.54.clone, metadata={op_name="jit(split_grads)/transpose(jvp(sl.device_stage))/while/body/closed_call/reduce_sum" stack_frame_id=116}
%fused_computation.1 (param_0.9: f32[32,4]) -> f32[4,32] {
"""


def test_hlo_text_gives_each_instruction_its_op_name():
    names = stages.op_names(HLO)
    assert names["frozen__embed__.1"] == "frozen['embed']"
    assert names["constant.213"] == ""
    assert set(names) == {"frozen__embed__.1", "constant.213",
                          "add_any.176", "multiply.82", "bitcast.482",
                          "reduce_sum.209"}
    got = {k: stages.stage(v) for k, v in names.items()}
    assert got == {
        "frozen__embed__.1": ("unscoped", "fwd"),
        "constant.213": ("unscoped", "fwd"),
        "add_any.176": ("sl.server_layers", "bwd"),
        "multiply.82": ("sl.link", "fwd"),
        "bitcast.482": ("sl.head", "fwd"),
        "reduce_sum.209": ("sl.device_stage", "bwd"),
    }


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/jvp(sl.a)/while/body/x", ("sl.a", "fwd")),
    ("jit(f)/transpose(jvp(sl.a))/while/body/x", ("sl.a", "bwd")),
    ("jit(f)/transpose(jvp(g))/sl.a/x", ("sl.a", "fwd")),
    ("jit(f)/transpose(jvp(g/sl.a))/x", ("sl.a", "bwd")),
    ("jit(f)/bqhgd,bkhd->bhgqk/transpose", ("unscoped", "fwd")),
])
def test_stage_takes_the_last_scope_and_its_direction(op_name, want):
    assert stages.stage(op_name) == want


def test_nested_op_events_count_once():
    ops = [("fusion.1", 12, 20), ("while.1", 10, 50), ("fusion.2", 20, 50),
           ("fusion.3", 50, 60), ("copy.1", 55, 58)]
    assert stages.outermost(ops) == [("while.1", 10, 50),
                                     ("fusion.3", 50, 60)]


def _raw(modules, ops, spans, completions=None):
    if completions is None:   # offset 0: each completes as it ends
        completions = {rid: e for _, _, e, rid in modules}
    return {"devices": [{"name": "/device:TPU:0", "modules": modules,
                         "ops": ops}],
            "spans": spans, "completions": completions}


def test_ops_are_grouped_by_the_program_that_encloses_them():
    raw = _raw([("jit_split_grads", 0, 100, 1), ("jit_add", 110, 120, 2)],
               [("while.1", 10, 50), ("fusion.2", 20, 30),
                ("fusion.3", 60, 70), ("add.1", 112, 118)],
               [[(W, 0, 200)]])
    names = {"while.1": "jit(split_grads)/transpose(jvp(sl.head))/while",
             "fusion.2": "jit(split_grads)/jvp(sl.head)/dot_general"}
    red = stages.reduce(raw, names)
    progs = red["programs"]
    assert progs["jit_split_grads"]["op_seconds"] == pytest.approx(50e-9)
    assert progs["jit_split_grads"]["seconds"] == pytest.approx(100e-9)
    assert progs["jit_add"]["op_seconds"] == pytest.approx(6e-9)
    assert dict(red["program_ops"]["jit_add"]) == {"add.1": pytest.approx(
        6e-9)}
    assert red["stages"] == {"sl.head.bwd": pytest.approx(40e-9)}
    assert red["unmapped"] == {"fusion.3": pytest.approx(10e-9)}
    m = stages.metrics(red)
    assert m["split_head_ms"] == pytest.approx(40e-6)
    assert m["split_programs_per_step"] == pytest.approx(2.0)


def test_gaps_land_on_the_innermost_span():
    spans = [[(W, 0, 1000), ("bench.split_run", 0, 1000),
              ("sl.round", 100, 900), ("sl.optimizer", 300, 500),
              ("other.span", 350, 360)]]
    raw = _raw([("jit_split_grads", 0, 100, 1), ("jit_a", 200, 300, 2),
                ("jit_b", 500, 950, 3)], [], spans)
    red = stages.reduce(raw, {})
    gaps = {g[0]: (g[1], g[2]) for g in red["idle_gaps"]}
    assert gaps == {"sl.round": (pytest.approx(100e-9), 1),
                    "sl.optimizer": (pytest.approx(200e-9), 1),
                    "bench.split_run": (pytest.approx(50e-9), 1)}
    assert red["idle_s"] == pytest.approx(350e-9)
    inf = stages.info(red)
    assert inf["idle_on_sl_spans"] == pytest.approx(300 / 350)
    assert inf["idle_on_bench_split_run"] == pytest.approx(50 / 350)


def test_gap_outside_every_span():
    raw = _raw([("jit_a", 0, 10, 1)], [], [[(W, 0, 30)]])
    assert stages.reduce(raw, {})["idle_gaps"][0][0] == \
        "outside benchmark spans"


def test_span_self_time_and_share_at_an_edge():
    # a 100 ns slice; A straddles its start, B (inside A) and C (inside B)
    lines = [[("sl.a", 50, 150), ("sl.b", 60, 140), ("sl.c", 110, 120),
              ("sl.d", 160, 170)]]
    tot = stages.span_totals(lines, 100, 200)
    assert tot["sl.a"]["seconds"] == pytest.approx(50e-9)
    assert tot["sl.a"]["self_seconds"] == pytest.approx(10e-9)
    assert tot["sl.a"]["count"] == pytest.approx(0.5)
    assert tot["sl.b"]["seconds"] == pytest.approx(40e-9)
    assert tot["sl.b"]["self_seconds"] == pytest.approx(30e-9)
    assert tot["sl.b"]["count"] == pytest.approx(0.5)
    assert tot["sl.c"]["count"] == pytest.approx(1.0)
    assert tot["sl.d"]["self_seconds"] == pytest.approx(10e-9)


def test_host_metrics_per_span_and_per_step():
    spans = [[(W, 0, 1000),
              ("sl.decide", 0, 40),
              ("sl.split_lora", 50, 60), ("sl.dispatch", 60, 70),
              ("sl.merge_lora", 70, 75), ("sl.optimizer", 80, 180),
              ("sl.split_lora", 200, 210), ("sl.dispatch", 210, 220),
              ("sl.merge_lora", 220, 225), ("sl.optimizer", 230, 330)]]
    raw = _raw([("jit_split_grads", 70, 170, 1),
                ("jit_split_grads", 220, 320, 2)], [], spans)
    m = stages.metrics(stages.reduce(raw, {}))
    assert m["split_optimizer_host_ms"] == pytest.approx(100e-6)
    assert m["split_adapters_host_ms"] == pytest.approx(15e-6)
    assert m["split_decide_host_ms"] == pytest.approx(40e-6)
    assert m["split_programs_per_step"] == pytest.approx(1.0)
    # no scope in the program: the device stage metrics read nothing
    for k in ("split_server_layers_fwd_ms", "split_server_layers_bwd_ms",
              "split_head_ms", "split_link_ms"):
        assert m[k] is None


def test_clock_offset_and_matches():
    raw = _raw([("jit_a", 0, 10, 1), ("jit_a", 20, 30, 2),
                ("jit_a", 40, 50, 3)], [], [[(W, 0, 100)]],
               completions={1: 17, 2: 35, 7: 0})
    assert stages.clock(raw) == {"offset_ns": 5.0, "matched": 2}


def test_no_matched_run_id_raises():
    raw = _raw([("jit_a", 0, 10, 1)], [], [[(W, 0, 100)]],
               completions={9: 12})
    with pytest.raises(ValueError, match="completion"):
        stages.clock(raw)
    with pytest.raises(ValueError, match="completion"):
        stages.reduce(raw, {})


def test_scope_map_refuses_a_name_two_cuts_disagree_on():
    a = '  %f.1 = f32[] add(%x, %y), metadata={op_name="jit(g)/sl.a/add"}\n'
    b = '  %f.1 = f32[] add(%x, %y), metadata={op_name="jit(g)/sl.b/add"}\n'
    assert stages.scope_map([a, a]) == {"f.1": "jit(g)/sl.a/add"}
    with pytest.raises(ValueError, match="differs"):
        stages.scope_map([a, b])


def test_programs_per_step_reader():
    reader = harness.load_module(
        os.path.join(harness.HERE, "metrics", "split_programs_per_step.py"),
        "chipbench_metric_split_programs_per_step")
    assert reader.read({}) is None
    assert reader.read({"trace": {"programs": {
        "jit_add": {"count": 10.0, "seconds": 1.0}}}}) is None
    assert reader.read({"trace": {"programs": {
        "jit_split_grads": {"count": 2.0, "seconds": 1.0},
        "jit_add": {"count": 10.0, "seconds": 1.0}}}}) == pytest.approx(6.0)


# --- the trace recorded on the chip ------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    raw = stages.read(os.path.join(DATA, "spans.xplane.pb"))
    with open(os.path.join(DATA, "spans.hlo.txt")) as f:
        names = stages.op_names(f.read())
    return raw, names, stages.reduce(raw, names, scoped="jit_step")


def test_recorded_clock_matches(recorded):
    _, _, red = recorded
    assert red["clock"]["matched"] > 0


def test_recorded_ops_are_all_in_the_scope_map(recorded):
    raw, names, red = recorded
    ops = raw["devices"][0]["ops"]
    assert len(stages.outermost(ops)) < len(ops)   # the loop holds its body
    assert red["unmapped"] == {}
    assert red["stages"]["sl.loop.fwd"] > 0
    assert red["stages"]["sl.loop.bwd"] > 0
    prog = red["programs"]["jit_step"]
    assert prog["count"] == pytest.approx(3)
    assert sum(red["stages"].values()) == pytest.approx(prog["op_seconds"])
    assert prog["op_seconds"] <= prog["seconds"]


def test_recorded_spans_and_gaps(recorded):
    _, _, red = recorded
    assert red["spans"]["sl.step"]["count"] == pytest.approx(3)
    assert red["spans"]["sl.host_pause"]["count"] == pytest.approx(3)
    assert red["spans"]["sl.host_pause"]["seconds"] >= 3 * 0.002
    gaps = {g[0]: g[1] for g in red["idle_gaps"]}
    assert gaps["sl.host_pause"] >= 3 * 0.002
    assert sum(gaps.values()) == pytest.approx(red["idle_s"])
    assert red["busy_s"] + red["idle_s"] == pytest.approx(red["window_s"])
