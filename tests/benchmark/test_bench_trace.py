"""The reduction from a trace to numbers: on hand-made events, and on slices of
traces recorded on the v5e (``benchmarks/fixtures``, PR 23) against values
counted by hand from the events."""

import pytest

from benchmarks.harness import costs, registry, trace
from benchmarks.harness.client import median

PROGRAMS = {"decode": "^jit__lambda", "prefill": "^jit_prefill_chunk_row"}


def _plane(modules, ops, name="/device:TPU:0"):
    return {"plane": name, "lines": {trace.MODULES_LINE: modules, trace.OPS_LINE: ops}}


@pytest.mark.parametrize(
    "intervals, total",
    [
        ([], 0),
        ([(0, 10)], 10),
        ([(0, 10), (10, 20)], 20),
        ([(0, 10), (5, 8)], 10),  # nested: once
        ([(0, 10), (5, 15), (20, 30)], 25),
        ([(20, 30), (0, 10), (5, 15)], 25),  # order does not matter
        ([(0, 100), (10, 20), (30, 40), (50, 120)], 120),
    ],
)
def test_union_counts_covered_time_once(intervals, total):
    assert trace.union_ns(intervals) == total


def test_busy_is_a_union_on_one_line_not_a_sum_across_lines_or_nested_operations():
    # A program of 100 ns whose while loop (80 ns) holds two fusions; then idle; then 50 ns.
    modules = [["jit__lambda(1)", 0, 100], ["jit_prefill_chunk_row(2)", 150, 50]]
    ops = [
        ["%while.1 while s32[]", 10, 80], ["%fusion.1 fusion f32[8]", 10, 30],
        ["%fusion.2 fusion f32[8]", 50, 40], ["%copy.1 copy f32[8]", 0, 10],
        ["%fusion.9 fusion f32[8]", 150, 50],
    ]
    d = trace.reduce_plane(_plane(modules, ops), PROGRAMS)
    assert d["window_s"] == pytest.approx(200e-9)
    assert d["busy_s"] == pytest.approx(140e-9)  # [0, 90) and [150, 200): not 210, the sum
    assert sum(x[2] for x in ops) == 210
    assert d["programs"] == {"decode": [pytest.approx(100e-9)], "prefill": [pytest.approx(50e-9)]}
    assert d["gaps"] == [[pytest.approx(100e-9), pytest.approx(50e-9), "decode->prefill"]]
    # The loop is not an operation that "took time": its children are.
    assert [n for n, _ in d["ops"]] == [
        "%fusion.9 fusion f32[8]", "%fusion.2 fusion f32[8]", "%fusion.1 fusion f32[8]", "%copy.1 copy f32[8]",
    ]


def test_four_devices_average_and_never_add_up():
    ops = [["%fusion.1 fusion f32[8]", 0, 60], ["%fusion.2 fusion f32[8]", 80, 20]]
    planes = [_plane([["jit_train_step(1)", 0, 100]], ops, f"/device:TPU:{i}") for i in range(4)]
    r = trace.reduce({"planes": planes}, {"train_step": "^jit_train_step"})
    assert len(r["devices"]) == 4
    assert r["window_s"] == pytest.approx(100e-9) and r["busy_s"] == pytest.approx(80e-9)
    assert r["busy_s"] <= r["window_s"]


def test_exposed_collective_time_is_what_no_other_operation_covers():
    ops = [
        ["%all-reduce.1 all-reduce f32[4096]", 0, 100],
        ["%fusion.1 fusion f32[8]", 20, 30],  # hides 30 of it
        ["%fusion.2 fusion f32[8]", 90, 40],  # hides 10 more
        ["%all-gather.2 all-gather f32[8]", 200, 10],
    ]
    d = trace.reduce_plane(_plane([["jit_train_step(1)", 0, 210]], ops), {})
    assert d["collective_s"] == pytest.approx(110e-9)
    assert d["collective_exposed_s"] == pytest.approx(70e-9)


def test_a_plane_without_operations_is_left_out_and_an_empty_trace_reads_zero():
    r = trace.reduce({"planes": [_plane([], [])]}, {})
    assert r == {"devices": [], "window_s": 0.0, "busy_s": 0.0}
    assert trace.breakdown(r) == {"device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize(
    "full, short",
    [
        ("%fusion.177 = f32[32]{0:T(128)S(1)} fusion(bf16[1,32,4096]{2,1,0:T(8,128)(2,1)} %p)", "%fusion.177 fusion f32[32]"),
        ("%while.1 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1:T(8,128)(2,1)S(1)}) while((s32[]) %t), body=%b", "%while.1 while s32[]"),
        ("%copy-done = bf16[2561,16,8,128]{3,2,1,0:T(8,128)(2,1)} copy-done((bf16[2561,16,8,128]) %c)", "%copy-done copy-done bf16[2561,16,8,128]"),
        ("jit_train_step(15221478436479372531)", "jit_train_step(15221478436479372531)"),
    ],
)
def test_an_operation_is_named_by_name_opcode_and_shape_not_by_its_whole_instruction(full, short):
    assert trace.short_name(full) == short


# -- the recorded slices -------------------------------------------------------


def test_serving_slice_against_hand_counted_values(fixture_raw, fixture_reduced):
    raw = fixture_raw("serve_slice.json")
    (plane,) = raw["planes"]
    assert plane["plane"] == "/device:TPU:0"
    r = fixture_reduced("serve_slice.json", "serve16.chat-open")
    (d,) = r["devices"]
    # Three engine iterations: decode, the host's turn, a prefill chunk.
    assert d["programs"]["decode"] == [pytest.approx(x) for x in (0.030804430, 0.030804787, 0.030799243)]
    assert d["programs"]["prefill"] == [pytest.approx(x) for x in (0.023325729, 0.023359121, 0.023324646)]
    # Gaps run from a named program's end to the next one's start, over the
    # four tiny dtype conversions between them: 51443038 - 30804430 ns, and
    # 76083168 - (51443038 + 23325729) ns.
    assert d["gaps"][0][1:] == [pytest.approx(0.020638608), "decode->prefill"]
    assert d["gaps"][1][1:] == [pytest.approx(0.001314401), "prefill->decode"]
    assert [g[2] for g in d["gaps"]] == ["decode->prefill", "prefill->decode"] * 2 + ["decode->prefill"]
    assert median([g[1] for g in d["gaps"]]) == pytest.approx(0.020271213)
    # The programs' own time is 162.43 ms of the slice's 226.72.
    programs_ns = sum(dur for _, _, dur in plane["lines"][trace.MODULES_LINE])
    assert programs_ns == 162_425_019
    assert 0 < d["busy_s"] <= programs_ns / 1e9 < d["window_s"] == pytest.approx(0.226720297)
    assert d["busy_s"] == pytest.approx(0.162414531)
    # A sum over the operations counts each loop and its children: more than the slice.
    assert sum(dur for _, _, dur in plane["lines"][trace.OPS_LINE]) / 1e9 > d["window_s"]
    assert r["busy_s"] == d["busy_s"] and r["window_s"] == d["window_s"]
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == "%bitcast_dynamic-update-slice_fusion.5 fusion bf16[16,2561,16,8,128]"
    assert b["idle_gaps"][0] == ["host between decode->prefill", pytest.approx(0.061427296)]
    assert all(len(name) <= 120 for name, _ in b["device_ops"])


def test_training_slice_against_hand_counted_values(fixture_raw, fixture_reduced):
    r = fixture_reduced("train_slice.json", "train2.dense-4k")
    (d,) = r["devices"]
    assert d["programs"] == {"train_step": [pytest.approx(0.166742170), pytest.approx(0.166741562)]}
    # 166747575 - 166742170 ns between the two steps.
    assert d["gaps"] == [[pytest.approx(0.166742170, abs=1e-6), pytest.approx(5.405e-6), "train_step->train_step"]]
    assert d["busy_s"] == pytest.approx(0.333478107) and d["window_s"] == pytest.approx(0.333486951)
    assert d["collective_s"] == 0.0
    assert d["ops"][0][0] == "%convolution_add_fusion.5 fusion f32[4096,32000]"
    assert not any(trace.opcode(name) == "while" for name, _ in d["ops"])


def test_four_chip_training_slice_against_hand_counted_values(fixture_raw, fixture_reduced):
    raw = fixture_raw("train_dp4_slice.json")
    assert [p["plane"] for p in raw["planes"]] == [f"/device:TPU:{i}" for i in range(4)]
    r = fixture_reduced("train_dp4_slice.json", "train2.dp4-4k")
    assert len(r["devices"]) == 4
    steps = [d["programs"]["train_step"][0] for d in r["devices"]]
    assert steps == [pytest.approx(x) for x in (0.354550253, 0.354561476, 0.354557649, 0.354545084)]
    # The step after it starts 354555204 - 354550253 ns later on chip 0.
    assert r["devices"][0]["gaps"] == [[pytest.approx(0.35455, abs=1e-5), pytest.approx(4.951e-6), "train_step->train_step"]]
    # One step's time, not four: the mean over the chips, each a union.
    assert r["window_s"] == pytest.approx(0.35452950075) and r["busy_s"] == pytest.approx(0.35452884475)
    assert r["busy_s"] <= r["window_s"] < 0.355
    for d, (coll, exposed) in zip(r["devices"], [(0.092263694,) * 2, (0.09227444,) * 2, (0.092287926,) * 2, (0.092280646,) * 2]):
        # Nothing runs beside a collective in this program: all of it is exposed.
        assert d["collective_s"] == pytest.approx(coll) and d["collective_exposed_s"] == pytest.approx(exposed)
    # Counted by hand on chip 0: 64 all-gathers of bf16[32,512,4096] take 72.59 ms of the 92.26.
    ops = raw["planes"][0]["lines"][trace.OPS_LINE]
    gathers = [d for n, _, d in ops if n in ("%all-gather.33 all-gather bf16[32,512,4096]", "%all-gather.31 all-gather bf16[32,512,4096]")]
    assert len(gathers) == 64 and sum(gathers) == 36339907 + 36252223


@pytest.mark.parametrize(
    "fixture, workload, batch, step_s, chips",
    [
        ("train_slice.json", "train2.dense-4k", 1, (0.166742170 + 0.166741562) / 2, 1),
        ("train_dp4_slice.json", "train2.dp4-4k", 4, (0.354550253 + 0.354557656) / 2, 4),  # chip 0
    ],
)
def test_mfu_is_needed_operations_over_the_traced_step_time_and_the_peak(
    manifest, fixture_reduced, fixture, workload, batch, step_s, chips
):
    """From the trace alone: no host clock, so the profiler's own cost (it
    writes its file inside a traced run) cannot enter it."""
    cell = registry.load_cell(manifest, workload)
    result = {
        "cell": cell, "train": {"batch": batch}, "trace": fixture_reduced(fixture, workload),
        "device": {"kind": "TPU v5 lite", "count": chips},
    }
    flops = batch * 14_766_298_890_240  # test_bench_costs: one sequence of 4096
    assert costs.train_step_flops(cell["config"], 4096, batch) == flops
    mfu = registry.load_metric("per_layer", "mfu_pct")(result)
    assert mfu == pytest.approx(100 * flops / step_s / (chips * 197e12))
    assert mfu == pytest.approx({1: 44.95, 4: 21.14}[chips], abs=0.01)
    # No train-step program in the slice: nothing to read, the metric is left out.
    result["trace"] = {"devices": [], "busy_s": 0.0, "window_s": 0.0}
    assert registry.load_metric("per_layer", "mfu_pct")(result) is None
