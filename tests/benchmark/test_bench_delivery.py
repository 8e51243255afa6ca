"""The seven metrics of a token's way back (PR 38): each reader on a result
written by hand, with answers reckoned from the stamps below without the
readers; what a program without the rings gives; the entries in
``BENCHMARK.json``; and a toy cell through ``run.measure``, traced, on the CPU.
"""

import array
import math
import time

import pytest
from bench_toy import toy_cell

from benchmarks import run as bench_run
from benchmarks.harness import contract, registry

SEVEN = {
    "itl_emit_p95_ms": ("LLM engine", "program_span", "itl_p95_ms", "ms"),
    "itl_socket_p95_ms": ("Serve", "program_span", "itl_p95_ms", "ms"),
    "deliver_wake_p95_ms": ("Serve", "program_span", "itl_p95_ms", "ms"),
    "deliver_pickup_p95_ms": ("Serve", "program_span", "itl_p95_ms", "ms"),
    "deliver_reply_p95_ms": ("Serve", "program_span", "itl_p95_ms", "ms"),
    "deliver_write_p95_ms": ("Serve", "program_span", "itl_p95_ms", "ms"),
    "replica_gc_pause_ms_per_s": ("LLM engine", "program_counter", "serve_tokens_per_s", "ms/s"),
}
CELLS = ["glm8.rollout-long", "trinity5.rollout-longctx"]
FIELDS = ["rid", "index", "t_emit_ns", "t_yield_ns", "t_asked_ns", "t_enter_ns", "t_sweep_ns", "t_got_ns", "t_wrote_ns"]
OPEN = 9_000_000_000  # the window: the second that ends at the newest stamp, 10 s
# Two streams, their records interleaved as the ring holds them; stamps in ms
# from the window's opening, None = not taken (a stream's last batch).
#        rid index emit yield asked enter sweep got   wrote
TOKENS = [
    (1, 0, -30, -29, -40, -39, -28, -26, -25.5),  # emitted before the window: left out, but the start of a gap into it
    (2, 0, 10, 11, 5, 6, 15, 17, 17.1),
    (1, 1, 10, 12, -25, -24, 13, 16, 16.5),
    (1, 2, 30, 31, 17, 18, 35, 36, 36.2),
    (2, 1, 30, 34, 18, 19, 35, 39, 39.4),
    (2, 2, 50, 51, 40, 41, 52, None, None),
    (1, 3, 50, 53, 37, 38, 54, None, None),
]
EXPECTED = {
    "itl_emit_p95_ms": 36.0,  # gaps 40, 20, 20 (stream 1) and 20, 20 (stream 2): 20 + 0.8 x 20
    "itl_socket_p95_ms": 40.03,  # 42 and 19.7 (stream 1), 22.3 (stream 2); the gaps into an unwritten token skipped
    "deliver_wake_p95_ms": 3.75,  # 2, 1, 3, 1, 4, 1
    "deliver_pickup_p95_ms": 4.0,  # 1, 4, 1, 4, 1, 1
    "deliver_reply_p95_ms": 3.85,  # 3, 1, 2, 4
    "deliver_write_p95_ms": 0.485,  # 0.5, 0.2, 0.1, 0.4
    "replica_gc_pause_ms_per_s": 80.0,  # 50 + 30 ms of the one second; the 80 ms before it left out
}


def _ns(ms):
    return 0 if ms is None else OPEN + round(ms * 1e6)


def _result(tokens=TOKENS, **spans):
    packed = array.array("q", [v for rid, index, *stamps in tokens for v in (rid, index, *map(_ns, stamps))])
    held = {
        "fields": {"iterations": ["t_start_ns", "llm.iteration"], "deliveries": list(FIELDS),
                   "gc": ["t_start_ns", "duration_ns", "collected"]},
        "iterations": [OPEN + 990_000_000, 10_000_000],
        "deliveries": packed.tobytes(),
        "gc": [[OPEN - 500_000_000, 80_000_000, 3], [OPEN + 100_000_000, 50_000_000, 7], [OPEN + 900_000_000, 30_000_000, 1]],
    }
    held.update(spans)
    return {"seconds": 1.0, "counters": {"spans": {k: v for k, v in held.items() if v is not None}}}


def _read(name, result):
    return registry.load_metric("per_layer", name)(result)


@pytest.mark.parametrize("name", list(SEVEN))
def test_a_reader_on_a_result_written_by_hand(name):
    assert _read(name, _result()) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", list(SEVEN))
def test_a_reader_without_its_ring_gives_none(name):
    """The parent commit under this PR's benchmark files: no such record."""
    ring = "gc" if name == "replica_gc_pause_ms_per_s" else "deliveries"
    assert _read(name, _result(**{ring: None})) is None
    assert _read(name, {"seconds": 1.0, "counters": {}}) is None and _read(name, {"seconds": 1.0}) is None
    assert _read(name, _result(iterations=[])) is None  # no stamp to hang the window on


def test_a_ring_that_holds_no_token_of_the_window_gives_none_and_no_collection_gives_zero():
    early = [t for t in TOKENS if t[2] < 0]
    for name in SEVEN:
        value = _read(name, _result(tokens=early, gc=[]))
        assert value == 0.0 if name == "replica_gc_pause_ms_per_s" else value is None, name


def test_a_stream_whose_every_token_is_its_last_batch_has_no_socket_gap():
    unwritten = [t[:7] + (None, None) for t in TOKENS]
    result = _result(tokens=unwritten)
    for name in ("itl_socket_p95_ms", "deliver_reply_p95_ms", "deliver_write_p95_ms"):
        assert _read(name, result) is None
    assert _read("itl_emit_p95_ms", result) == pytest.approx(36.0)
    assert _read("deliver_pickup_p95_ms", result) == pytest.approx(4.0)


def test_a_gap_needs_two_successive_tokens_of_one_stream():
    """A token the ring has overwritten leaves a hole: no gap is reckoned across it."""
    holed = [t for t in TOKENS if (t[0], t[1]) != (1, 1)]
    assert _read("itl_emit_p95_ms", _result(tokens=holed)) == pytest.approx(20.0)  # 20 (1: 2->3), 20, 20 (2)


def test_the_seven_are_declared_for_exactly_the_two_cells(manifest):
    declared = {m["name"]: m for m in manifest["per_layer"]}
    reports = {m["name"]: set(m.get("workloads") or [w["name"] for w in manifest["workloads"]]) for m in manifest["end_to_end"]}
    for name, (layer, source, moves, unit) in SEVEN.items():
        entry = declared[name]
        assert entry == dict(name=name, unit=unit, better="lower", source=source, layer=layer, moves=moves, workloads=CELLS)
        assert set(CELLS) <= reports[moves]  # both cells report the end-to-end metric it should move
    # appended: the driver reads an entry put in the middle as a change to the one it displaces
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-7:] == list(SEVEN) and names[-9:-7] == ["cache_attention_ms", "cache_attention_roofline"]
    assert len(names) == 41 and len(set(names)) == 41
    for w in manifest["workloads"]:
        traced = set(contract.expected_metrics(manifest, w["name"], traced=True))
        assert (set(SEVEN) <= traced) == (w["name"] in CELLS) and (not set(SEVEN) & traced) == (w["name"] not in CELLS)
        assert not set(SEVEN) & set(contract.expected_metrics(manifest, w["name"], traced=False))


def test_trinitys_mix_is_the_issues_with_the_seven_behind_its_cache_pair(manifest):
    """Stands in for test_bench_trinity.py::test_the_mix_is_the_issues, which holds ``per_layer`` to END with
    PR 35's cache pair and is marked xfail (strict) in tests/conftest.py since PR 38 appends its seven behind
    the pair. Everything else that test holds is held here, and the pair where it now stands."""
    trinity, glm = "trinity5.rollout-longctx", "glm8.rollout-long"
    rollout = registry.load_cell(manifest, trinity)["traffic"]
    assert rollout["arrival"] == {"process": "closed", "clients": 32, "requests_per_client": 4}
    assert rollout["sampling"] == {"sampled_share": 1.0, "temperature": 1.0, "top_k": 0}
    assert rollout["stratified"] is True and rollout["schedule_seed"] == 35
    assert (rollout["grace_s"], rollout["trace_slice_s"]) == (5.0, 3.0) and 16.0 <= rollout["preroll_s"] <= 24.0
    lo, hi = rollout["prompt_len"]["min"], rollout["prompt_len"]["max"]
    assert rollout["prompt_len"]["dist"] == rollout["output_len"]["dist"] == "uniform"
    assert hi - lo == 512 and abs(lo - 3072) <= 512 and "lengths_why" in rollout
    want = lambda cell, traced: set(contract.expected_metrics(manifest, cell, traced))  # noqa: E731
    assert want(trinity, False) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    pair = ["cache_attention_ms", "cache_attention_roofline"]
    assert want(trinity, True) == (want(glm, True) - {"latent_attention_ms", "latent_attention_roofline"}) | set(pair)
    for w in manifest["workloads"]:
        assert bool(set(pair) & want(w["name"], True)) == (w["name"] == trinity)
    assert [m["name"] for m in manifest["per_layer"][-9:-7]] == pair
    assert manifest["workloads"][-1]["name"] == trinity and manifest["workloads"][-1]["chips"] == 1
    assert len(manifest["workloads"]) == 7 and [c["name"] for c in manifest["configs"]][-1] == "trinity-mini-serve5"


def test_a_toy_cell_traced_carries_all_seven(manifest, fake_chips, tmp_path):
    """``glm8.rollout-long`` at a toy size through ``run.measure`` with the
    profiler's slice and the 1 Hz poll, on the CPU: the line of the traced run
    holds the seven as finite numbers, the untraced line's shape is the parent's."""
    cell = toy_cell(manifest, CELLS[0])
    cell["traffic"]["arrival"]["clients"] = 4  # as many as the toy engine's slots, as in the cell
    result = bench_run.measure(
        cell, seed=2**31 + 38, seconds=3.0, traced=True, t_process=time.monotonic(),
        scratch=str(tmp_path / "scratch"), platform="cpu",
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    for name in SEVEN:
        value = _read(name, result)
        assert value is not None and math.isfinite(value) and value >= 0.0, (name, value)
    assert _read("itl_emit_p95_ms", result) > 0 and _read("deliver_pickup_p95_ms", result) > 0
    spans = result["counters"]["spans"]
    assert isinstance(spans["deliveries"], bytes) and len(spans["deliveries"]) % (8 * len(FIELDS)) == 0
    assert spans["fields"]["deliveries"] == FIELDS
    untraced = bench_run.build_line(manifest, dict(result, traced=False))
    assert set(untraced["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s", "setup_s"}
