"""serve.llm spans (ISSUE 24): request stamps, iteration records, the compile
ring, the profiler's host plane and the /metrics fold, on the CPU backend with
the tiny model of ``test_serve_llm_engine.py``.

Everything but the last test runs with no cluster; the engines share their
compiled programs through the engine's process-level jit cache.
"""

import glob
import json
import time
import types
import urllib.request

import numpy as np
import pytest

from ray_tpu.serve._private.replica import SETUP_STAMPS
from ray_tpu.serve.llm import stats

MODEL = dict(
    vocab_size=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=64, dtype="float32", remat=False,
)
ENGINE = dict(num_slots=3, block_size=4, max_model_len=32, prefill_chunk=4)
COL = {name: i for i, name in enumerate(stats.REQUEST_FIELDS)}
IT = {name: i for i, name in enumerate(stats.ITERATION_FIELDS)}
PHASES = [n for n in stats.SPAN_NAMES if n != "llm.iteration"]


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**dict(MODEL, dtype=jnp.dtype(MODEL["dtype"]).type))
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(model, **over):
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    return LLMEngine(params, cfg, **dict(ENGINE, **over))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, MODEL["vocab_size"], n).tolist()


def _iterations(exported):
    """The exported iteration ring, one flat list, as rows."""
    flat, width = exported["iterations"], len(exported["fields"]["iterations"])
    assert len(flat) % width == 0 and all(isinstance(v, int) for v in flat)
    return [flat[i:i + width] for i in range(0, len(flat), width)]


def _record(eng, req, timeout=10.0):
    """The ended request's record as a dict (the scheduler writes it a moment
    after the consumer sees the terminal event)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for rec in eng.spans.export()["requests"]:
            if rec[COL["rid"]] == req.id:
                return dict(zip(stats.REQUEST_FIELDS, rec))
        time.sleep(0.01)
    raise AssertionError(f"no record of {req.id}")


# ---------------------------------------------------------------------------
# request records
# ---------------------------------------------------------------------------


def _finished(eng):
    req = eng.submit(_prompt(1, 9), max_new_tokens=5, request_id="abc",
                     t_recv_ns=time.monotonic_ns())
    assert len(req.result(timeout=60)) == 5
    return req, dict(outcome="finished", generated=5, prompt_tokens=9, request_id="abc")


def _cancelled(eng):
    req = eng.submit(_prompt(2, 6), max_new_tokens=24, t_recv_ns=time.monotonic_ns())
    next(iter(req))  # first token is out
    eng.cancel(req)
    return req, dict(outcome="cancelled", prompt_tokens=6)


def _preempted(eng):
    # Two 10-token prompts that grow to 22 tokens each (6 blocks) in a pool
    # of 9: the younger one is preempted mid-decode and recomputed.
    reqs = [eng.submit(_prompt(30 + i, 10), max_new_tokens=12,
                       t_recv_ns=time.monotonic_ns()) for i in range(2)]
    for r in reqs:
        assert len(r.result(timeout=120)) == 12
    assert eng.stats()["preemptions"] >= 1
    victim = max(reqs, key=lambda r: r.preemptions)
    return victim, dict(outcome="finished", generated=12, preemptions=victim.preemptions)


def _prefix_hit(eng):
    system = _prompt(4, 8)
    eng.submit(system + [3], max_new_tokens=2).result(timeout=60)
    req = eng.submit(system + [5], max_new_tokens=2, t_recv_ns=time.monotonic_ns())
    req.result(timeout=60)
    return req, dict(outcome="finished", cached_tokens=8, prompt_tokens=9)


@pytest.mark.parametrize(
    "scenario,engine_kw",
    [(_finished, {}), (_cancelled, {}), (_preempted, dict(num_blocks=10)), (_prefix_hit, {})],
    ids=["finished", "cancelled", "preempted_and_readmitted", "prefix_hit"],
)
def test_request_stamps_are_ordered(model, scenario, engine_kw):
    eng = _engine(model, **engine_kw)
    try:
        req, want = scenario(eng)
        rec = _record(eng, req)
    finally:
        eng.shutdown()
    stamps = [rec[k] for k in ("t_recv_ns", "t_submit_ns", "t_admit_ns", "t_first_ns", "t_done_ns")]
    assert all(isinstance(s, int) and s > 0 for s in stamps), rec
    assert stamps == sorted(stamps), rec
    assert rec["t_admit_ns"] == round(req.t_admit * 1e9)  # the FIRST admission's (rounded as ``end_request`` rounds it: ``int`` is a nanosecond short now and then)
    for key, value in want.items():
        assert rec[key] == value, (key, rec)
    if want.get("preemptions"):
        assert rec["preemptions"] >= 1


def test_a_stamp_of_another_hosts_clock_is_dropped(model):
    eng = _engine(model)
    try:
        req = eng.submit(_prompt(5, 5), max_new_tokens=2,
                         t_recv_ns=time.monotonic_ns() + int(3600e9))
        req.result(timeout=60)
        rec = _record(eng, req)
    finally:
        eng.shutdown()
    assert rec["t_recv_ns"] == 0 and rec["t_submit_ns"] > 0


# ---------------------------------------------------------------------------
# iteration records
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("streams", [1, 3])
def test_iteration_phases_and_rows(model, streams):
    """The phases of an iteration sum to no more than ``llm.iteration``;
    ``rows`` is the slots that decoded: over a run, every token but each
    request's first (which its last prefill chunk yields) came from one row
    of one decode step; ``prefill_tokens`` adds up to the prompts."""
    eng = _engine(model)
    try:
        reqs = [eng.submit(_prompt(40 + i, 9), max_new_tokens=16) for i in range(streams)]
        for r in reqs:
            assert len(r.result(timeout=60)) == 16
    finally:
        eng.shutdown()  # joins the scheduler: its last pass has left its record
    out, totals = eng.spans.export(), eng.stats()
    its = _iterations(out)
    assert out["fields"]["iterations"] == list(stats.ITERATION_FIELDS) and its
    for r in its:
        assert sum(r[IT[p]] for p in PHASES) <= r[IT["llm.iteration"]], r
        assert 0 <= r[IT["rows"]] <= streams and r[IT["running"]] <= streams
        assert r[IT["rows"]] or r[IT["prefill_tokens"]]  # a pass that dispatched nothing leaves none
        assert (r[IT["llm.decode.fetch"]] > 0) == (r[IT["rows"]] > 0)
        assert r[IT["view_blocks"]] == (eng.n_max if r[IT["rows"]] else 0)  # n_max 8: one rung
    assert sum(r[IT["rows"]] for r in its) == streams * (16 - 1)
    assert sum(r[IT["prefill_tokens"]] for r in its) == streams * 9
    assert max(r[IT["rows"]] for r in its) == streams
    starts = [r[IT["t_start_ns"]] for r in its]
    assert starts == sorted(starts)  # oldest first
    # stats() carries the same, cumulated, as plain ints
    assert sum(totals["iterations"].values()) == len(its)
    for name in stats.SPAN_NAMES:
        assert totals["span_ns"][name] == sum(r[IT[name]] for r in its)
    kinds = ["mixed" if r[IT["rows"]] and r[IT["prefill_tokens"]] else "decode" if r[IT["rows"]] else "prefill" for r in its]
    assert totals["iterations"] == {kind: kinds.count(kind) for kind in stats.ITERATION_KINDS}


def test_view_blocks_is_the_width_of_the_pass_s_decode_step(model):
    """``view_blocks``: the rung of the step's block table, 0 on a pass without
    a decode step. With ``n_max`` 32 blocks the rungs are 16 and 32: a stream
    that passes 64 tokens moves from one to the other, and the ring's counts
    are ``stats()["decode_width_steps"]``."""
    eng = _engine(model, max_model_len=128, prefill_chunk=8)
    try:
        assert len(eng.submit(_prompt(45, 50), max_new_tokens=30).result(timeout=60)) == 30
    finally:
        eng.shutdown()
    its = _iterations(eng.spans.export())
    widths = [r[IT["view_blocks"]] for r in its]
    assert all((w > 0) == (r[IT["rows"]] > 0) for w, r in zip(widths, its))
    stepped = [w for w in widths if w]
    assert sorted(set(stepped)) == [16, 32] and stepped == sorted(stepped)  # up once, at 64 tokens
    assert stepped.count(16) == 64 - 50  # write positions 50..63 sit in the first 16 blocks
    assert eng.stats()["decode_width_steps"] == {16: stepped.count(16), 32: stepped.count(32)}
    assert any(r[IT["prefill_tokens"]] and not r[IT["view_blocks"]] for r in its)  # a chunk alone


def test_a_decode_iteration_with_a_step_in_flight_has_one_span_of_each_name(model):
    """Driven by hand, pass by pass as ``_loop`` does: a pass that finds a step
    in flight and runs no chunk opens exactly one ``llm.decode.build`` and one
    ``llm.decode.dispatch`` (of the step it launches), one ``llm.decode.fetch``,
    one ``llm.sample`` and one ``llm.emit`` (of the step it lands), and its
    record carries the rows, the width and the context of the step whose
    tokens it emits, not of the one it launched."""
    eng = _engine(model, max_model_len=128, prefill_chunk=8)
    eng.shutdown()
    eng._crashed = None
    reqs = [eng.submit(_prompt(46 + i, 40 + 9 * i), max_new_tokens=60 - 20 * i) for i in range(3)]
    opened, span = [], eng.spans.span
    eng.spans.span = lambda name, **args: (opened.append(name), span(name, **args))[1]
    checked = 0
    for _ in range(200):
        if all(r._finished for r in reqs):
            break
        landing, before = eng._inflight, eng.spans.iterations.n
        if landing is not None:
            rows = len(landing.reqs)
            context = sum(r._sched_pos + 1 for r in landing.reqs)  # each row's length, the token fed included
        del opened[:]
        it = eng.spans.begin(len(eng._waiting), sum(r is not None for r in eng._slots))
        with eng.spans.span("llm.admit") as sp:
            sp.set(admitted=eng._admit(), waiting=len(eng._waiting))
        eng._prefill_tick()
        eng._decode_tick()
        eng.spans.end(it)
        if landing is None or "llm.prefill.dispatch" in opened:
            continue
        assert sorted(opened) == sorted(
            ["llm.admit", "llm.decode.fetch", "llm.sample", "llm.emit"]
            + ["llm.decode.build", "llm.decode.dispatch"] * (eng._inflight is not None)
        ), opened
        (rec,) = eng.spans.iterations.since(before)
        assert rec[IT["rows"]] == rows and rec[IT["view_blocks"]] == landing.width
        assert rec[IT["context_tokens"]] == context
        assert all(rec[IT[name]] > 0 for name in opened)
        checked += 1
    assert all(r._finished for r in reqs) and eng._inflight is None
    assert checked >= 20
    widths = {r[IT["view_blocks"]] for r in eng.spans.iterations.since()}
    assert widths == {0, 16, 32}  # the 58-token prompt crosses 64 tokens: both rungs were landed


def test_stats_count_how_often_the_loop_runs_ahead(model):
    """``stats()`` has the three counters as plain ints (the benchmark logs a
    run's integer counters), and with every slot full all but the priming step
    are dispatched while their predecessor is unfetched."""
    eng = _engine(model)
    try:
        reqs = [eng.submit(_prompt(47 + i, 6), max_new_tokens=26) for i in range(ENGINE["num_slots"])]
        for r in reqs:
            assert len(r.result(timeout=60)) == 26
        late = eng.submit(_prompt(55, 6), max_new_tokens=26)
        next(iter(late))
        eng.cancel(late)
        deadline = time.monotonic() + 10
        while eng.stats()["running"] and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        eng.shutdown()
    s = eng.stats()
    for name in ("decode_steps", "decode_steps_run_ahead", "decode_rows_dropped"):
        assert type(s[name]) is int, (name, s[name])
    assert s["decode_steps"] == sum(s["decode_width_steps"].values()) >= 25 + 2
    assert s["decode_steps_run_ahead"] / s["decode_steps"] > 0.9
    # The cancelled stream was decoding when the sweep found it (unless it had
    # ended already): the row its step in flight carried was dropped, and a
    # landed row is an emitted token or a dropped id.
    assert s["decode_rows_dropped"] == s["cancelled"] <= 1
    assert sum(r[IT["rows"]] for r in _iterations(eng.spans.export())) == (
        ENGINE["num_slots"] * 25 + late.num_generated - 1 + s["decode_rows_dropped"]
    )


# ---------------------------------------------------------------------------
# the rings hold their size
# ---------------------------------------------------------------------------


def _fill_iterations(n):
    rec = stats.EngineSpans()
    for i in range(n):
        it = rec.begin(waiting=0, running=1)
        rec.carried(rows=1 + i % 3)
        rec.end(it)
    held = _iterations(rec.export())
    return held, [r[IT["rows"]] for r in held], [1 + i % 3 for i in range(n)]


def _fill_requests(n):
    rec = stats.EngineSpans()
    for i in range(n):
        rec.end_request(
            types.SimpleNamespace(
                id=f"llm-{i}", request_id="", trace_id="", span_id="", t_recv=None,
                t_submit=1.0, t_admit=1.1, t_first=1.2, t_done=1.3, prompt=[1, 2],
                cached_tokens=0, _sched_generated=[3], preemptions=0,
            ),
            "finished",
        )
    held = rec.export()["requests"]
    return held, [r[COL["rid"]] for r in held], [f"llm-{i}" for i in range(n)]


def _fill_compiles(n):
    for i in range(n):
        stats._on_compile_event("/jax/core/compile/backend_compile_duration", 0.5, fun_name=f"jit(f{i})")
    stats._on_compile_event("/jax/core/compile/jaxpr_trace_duration", 0.5)  # not a compile: ignored
    held = stats.compile_records()
    return held, [r[3] for r in held], [f"jit(f{i})" for i in range(n)]


@pytest.mark.parametrize(
    "fill,size",
    [(_fill_iterations, stats.ITERATION_RING), (_fill_requests, stats.REQUEST_RING),
     (_fill_compiles, stats.COMPILE_RING)],
    ids=["iterations_2048", "requests_512", "compiles_256"],
)
def test_a_ring_stays_at_its_size_under_three_times_as_many_events(fill, size):
    assert size in (2048, 512, 256)
    held, got, pushed = fill(3 * size)
    assert len(held) == size
    assert got == pushed[-size:]  # the newest, oldest first


# ---------------------------------------------------------------------------
# the profiler's host plane
# ---------------------------------------------------------------------------

SPAN_ARGS = {
    "llm.iteration": {"rows", "prefill_tokens"},
    "llm.admit": {"admitted", "waiting"},
    "llm.prefill.build": {"rid", "pos"},
    "llm.prefill.dispatch": {"rid"},
    "llm.prefill.fetch": {"rid"},
    "llm.decode.build": {"rows"},
    "llm.decode.dispatch": set(),
    "llm.decode.fetch": set(),
    "llm.sample": {"rows", "sampled", "top_k"},
    "llm.emit": {"tokens", "finished"},
}


@pytest.fixture(scope="module")
def host_plane(model, tmp_path_factory):
    """name -> list of argument dicts, of the ``llm.*`` events in the
    ``/host:CPU`` plane of a profile taken round a few iterations."""
    import jax
    from jax.profiler import ProfileData

    log_dir = str(tmp_path_factory.mktemp("profile"))
    eng = _engine(model)
    try:
        eng.submit(_prompt(50, 6), max_new_tokens=3).result(timeout=60)  # programs are built
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            # Eight tokens each: the first stream runs a step ahead of what it
            # has emitted, and is still decoding when the second joins it.
            reqs = [eng.submit(_prompt(51 + i, 6), max_new_tokens=8, temperature=0.8 * i,
                               top_k=5 * i, seed=i) for i in range(2)]
            for r in reqs:
                assert len(r.result(timeout=60)) == 8
            time.sleep(0.2)  # the scheduler closes its last pass after the consumer has its tokens
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("llm."):
                    events.setdefault(e.name, []).append(
                        dict(dict(e.stats), _start=e.start_ns, _dur=e.duration_ns)
                    )
    return events


def test_the_span_table_is_the_code_s():
    assert set(SPAN_ARGS) == set(stats.SPAN_NAMES)


@pytest.mark.parametrize("name", list(SPAN_ARGS))
def test_a_profile_holds_the_span_with_its_arguments(host_plane, name):
    assert name in host_plane, sorted(host_plane)
    for args in host_plane[name]:
        assert SPAN_ARGS[name] <= set(args), (name, args)
    if name == "llm.sample":
        # one span a step, not one a row: two rows decoded together at least once
        assert max(a["rows"] for a in host_plane[name]) == 2
        assert max(a["sampled"] for a in host_plane[name]) == 1
    if name == "llm.iteration":
        # the parent: every phase's event lies inside an iteration's
        spans = sorted((a["_start"], a["_start"] + a["_dur"]) for a in host_plane[name])
        for phase in PHASES:
            for a in host_plane.get(phase, []):
                assert any(lo <= a["_start"] and a["_start"] + a["_dur"] <= hi for lo, hi in spans)


# ---------------------------------------------------------------------------
# the sample/emit split changed no token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "sampling",
    [dict(temperature=0.0), dict(temperature=0.9, top_k=7), dict(temperature=1.0)],
    ids=["greedy", "seeded_top_k", "seeded"],
)
def test_batched_rows_draw_the_tokens_each_draws_alone(model, sampling):
    """All rows of a step are now drawn before any is emitted. A draw is keyed
    by (seed, position), so three requests decoding side by side give exactly
    what each gives alone; greedy is also the dense ``generate()`` oracle."""
    prompts = [_prompt(60 + i, 5 + 2 * i) for i in range(3)]
    eng = _engine(model)
    try:
        alone = [eng.submit(p, max_new_tokens=8, seed=7 + i, **sampling).result(timeout=60)
                 for i, p in enumerate(prompts)]
        reqs = [eng.submit(p, max_new_tokens=8, seed=7 + i, **sampling)
                for i, p in enumerate(prompts)]
        together = [r.result(timeout=60) for r in reqs]
    finally:
        eng.shutdown()
    rows = max(r[IT["rows"]] for r in _iterations(eng.spans.export()))
    assert together == alone
    assert rows == 3  # they did share steps
    if sampling["temperature"] == 0.0:
        import jax.numpy as jnp

        from ray_tpu.models.generate import generate

        params, cfg = model
        for p, toks in zip(prompts, together):
            dense = generate(params, jnp.asarray([p], jnp.int32), cfg, max_new_tokens=8, temperature=0.0)
            assert np.asarray(dense)[0].tolist() == toks


# ---------------------------------------------------------------------------
# compilations, set-up, /metrics
# ---------------------------------------------------------------------------


def test_a_compile_forced_by_a_new_shape_appears_in_compiles():
    import jax
    import jax.numpy as jnp

    stats.listen_for_compiles()
    stats.listen_for_compiles()  # once per process, however often it is asked

    def a_new_program_for_the_spans_test(x):
        return x * 3 + 1

    f = jax.jit(a_new_program_for_the_spans_test)
    t0 = time.monotonic_ns()
    f(jnp.ones((7,))).block_until_ready()
    mine = [r for r in stats.compile_records() if "a_new_program_for_the_spans_test" in r[3]]
    assert len(mine) == 1 and mine[0][2] == "backend_compile"
    assert t0 <= mine[0][0] <= time.monotonic_ns() and mine[0][1] > 0
    f(jnp.ones((7,))).block_until_ready()  # the same shape: nothing new
    f(jnp.ones((9,))).block_until_ready()  # a new shape: compiled again
    mine = [r for r in stats.compile_records() if "a_new_program_for_the_spans_test" in r[3]]
    assert len(mine) == 2


def test_get_stats_carries_the_records_the_setup_and_the_proxys_stamps():
    from ray_tpu.serve._private.common import RECV_STAMP_HEADER, REQUEST_ID_HEADER
    from ray_tpu.serve._private.replica import HTTPRequest
    from ray_tpu.serve.llm import LLMDeployment

    dep = LLMDeployment(MODEL, engine_config=ENGINE)
    try:
        t_recv = time.monotonic_ns()
        body = json.dumps({"tokens": _prompt(70, 6), "max_new_tokens": 3, "stream": False})
        out = dep(HTTPRequest("POST", "/", {}, body.encode(),
                              {REQUEST_ID_HEADER: "req-7", RECV_STAMP_HEADER: str(t_recv)}))
        assert len(out["tokens"]) == 3
        deadline = time.monotonic() + 10
        while not dep.get_stats()["spans"]["requests"] and time.monotonic() < deadline:
            time.sleep(0.01)
        # The pass that ended the request counts itself after it pushed the
        # request's record: wait for it, so that the two reads below agree. The
        # engine's read comes a while after the deployment's: two reads taken
        # together agree also when neither has the count yet.
        while True:
            got = dep.get_stats()
            time.sleep(0.1)
            if got["iterations"] == dep.engine.stats()["iterations"] or time.monotonic() > deadline:
                break
    finally:
        dep.prepare_for_shutdown()
    spans = got["spans"]
    assert set(spans) == {
        "iterations", "requests", "compiles", "deliveries", "gc", "gc_younger", "setup", "fields",
        "stages", "setup_stamps", "compile_totals", "build_threads",
    }
    assert spans["fields"]["stages"] == list(stats.STAGE_FIELDS)
    assert [r[0] for r in spans["stages"][:5]] == ["jax_import", "backend", "params", "pool", "jit_build"]
    assert spans["setup_stamps"] == SETUP_STAMPS  # a copy of this process's: no replica wraps this deployment
    assert set(spans["compile_totals"]) == {"backend_compile", "cache_retrieval", "compiled_afresh"}
    assert spans["compile_totals"]["backend_compile"][0] >= len(spans["compiles"]) > 0
    assert all(isinstance(name, str) and ns > 0 for name, ns in spans["build_threads"])
    assert len(spans["build_threads"]) <= 5
    assert set(spans["setup"]) == {
        "jax_import_s", "backend_s", "params_s", "pool_s", "jit_build_s", "decode_build_s", "fused_build_s",
    }
    assert all(isinstance(v, float) and v >= 0 for v in spans["setup"].values())
    (rec,) = spans["requests"]
    rec = dict(zip(spans["fields"]["requests"], rec))
    assert rec["request_id"] == "req-7" and rec["t_recv_ns"] == t_recv
    assert rec["t_recv_ns"] <= rec["t_submit_ns"] <= rec["t_admit_ns"] <= rec["t_first_ns"]
    assert any(c[2] == "backend_compile" for c in spans["compiles"])
    # plain lists, strings and numbers: nothing of NumPy crosses the RPC; the delivery ring as packed bytes
    assert spans["deliveries"] == b""  # nothing was streamed
    json.dumps({ring: recs for ring, recs in spans.items() if ring != "deliveries"})
    assert got["iterations"] == dep.engine.stats()["iterations"]


# ---------------------------------------------------------------------------
# a start, stage by stage and program by program (ISSUE 52)
# ---------------------------------------------------------------------------

STAGE = {name: i for i, name in enumerate(stats.STAGE_FIELDS)}
BUILD_STAGES = ("trace", "lower", "compile", "first_run")


def _wall(rec):
    return rec[STAGE["t_end_ns"]] - rec[STAGE["t_start_ns"]]


@pytest.fixture(scope="module")
def built(model):
    """One engine whose shape fuses (five programs) and its stage records."""
    eng = _engine(model)
    try:
        yield eng, [tuple(r) for r in eng.spans.export()["stages"]]
    finally:
        eng.shutdown()


def test_stage_records_name_every_program_of_the_build_with_its_four_stages(built):
    eng, stages = built
    programs = [f"decode@{w}" for w in eng._view_rungs] + ["prefill", "decode_with_chunk"]
    assert eng._fuses and len(programs) >= 2
    assert [r[0] for r in stages if not r[STAGE["program"]]] == ["pool", "jit_build"]
    of_programs = [(r[STAGE["program"]], r[STAGE["stage"]]) for r in stages if r[STAGE["program"]]]
    assert sorted(of_programs) == sorted((p, s) for p in programs for s in BUILD_STAGES)
    # traced and lowered one after another, program by program, then run in the same order
    python = [(p, s) for p in programs for s in ("trace", "lower")]
    assert [x for x in of_programs if x[1] in ("trace", "lower")] == python
    assert [p for p, s in of_programs if s == "first_run"] == programs


@pytest.mark.parametrize("field", ["t_start_ns", "cpu_ns", "process_cpu_ns", "gc_ns", "gc2"])
def test_a_stage_records_ints_that_do_not_run_backwards(built, field):
    _, stages = built
    for rec in stages:
        assert len(rec) == len(stats.STAGE_FIELDS)
        assert all(isinstance(rec[STAGE[f]], str) for f in ("stage", "program", "thread"))
        assert isinstance(rec[STAGE[field]], int) and rec[STAGE[field]] >= 0
    if field == "t_start_ns":
        assert all(0 < r[STAGE["t_start_ns"]] <= r[STAGE["t_end_ns"]] for r in stages)
    if field == "cpu_ns":  # a thread burns no more than the process it belongs to (the clocks' grain apart)
        assert all(r[STAGE["cpu_ns"]] <= r[STAGE["process_cpu_ns"]] + 20_000_000 for r in stages)
    if field == "gc_ns":  # the collector cannot have run for longer than the stage lasted
        assert all(r[STAGE["gc_ns"]] <= _wall(r) for r in stages if r[STAGE["thread"]] == stages[0][STAGE["thread"]])


def test_the_building_threads_stages_do_not_overlap_and_the_pool_compiles(built):
    _, stages = built
    builder = stages[0][STAGE["thread"]]
    mine = sorted((r for r in stages if r[STAGE["thread"]] == builder), key=lambda r: r[STAGE["t_start_ns"]])
    assert all(a[STAGE["t_end_ns"]] <= b[STAGE["t_start_ns"]] for a, b in zip(mine, mine[1:]))
    assert {r[STAGE["stage"]] for r in mine} == {"pool", "jit_build", "trace", "lower", "first_run"}
    compiles = [r for r in stages if r[STAGE["stage"]] == "compile"]
    assert len(compiles) >= 2 and all(r[STAGE["thread"]] != builder for r in compiles)
    # side by side: the compiles' wall, first start to last end, is less than their sum, or no longer than the longest
    python_end = max(r[STAGE["t_end_ns"]] for r in stages if r[STAGE["stage"]] == "lower")
    first_run = min(r[STAGE["t_start_ns"]] for r in stages if r[STAGE["stage"]] == "first_run")
    assert all(python_end <= r[STAGE["t_start_ns"]] and r[STAGE["t_end_ns"]] <= first_run for r in compiles)


def test_the_seven_seconds_of_setup_are_the_sums_of_their_records(built):
    eng, stages = built
    setup = eng.spans.setup
    of_programs = [r for r in stages if r[STAGE["program"]]]
    fused = sum(
        _wall(r) for r in of_programs
        if r[STAGE["program"]] == "decode_with_chunk" and r[STAGE["stage"]] in ("trace", "lower")
    )
    whole = max(r[STAGE["t_end_ns"]] for r in of_programs) - min(r[STAGE["t_start_ns"]] for r in of_programs)
    assert setup["fused_build_s"] == fused / 1e9 > 0
    assert setup["decode_build_s"] == (whole - fused) / 1e9 > 0
    for name in ("pool", "jit_build"):
        (rec,) = [r for r in stages if r[STAGE["stage"]] == name]
        assert setup[name + "_s"] == _wall(rec) / 1e9
    assert set(setup) == {"pool_s", "jit_build_s", "decode_build_s", "fused_build_s"}  # the deployment adds its three
    assert stats.setup_seconds([]) == {}


def test_an_engine_that_builds_one_rung_a_program_keeps_no_fused_seconds(model):
    eng = _engine(model, num_slots=200)  # more rows than one tile of the matrix unit: no step with a chunk
    try:
        assert not eng._fuses
        stages = eng.spans.export()["stages"]
        assert {r[STAGE["program"]] for r in stages if r[STAGE["program"]]} == {f"decode@{w}" for w in eng._view_rungs}
        assert "fused_build_s" not in eng.spans.setup and eng.spans.setup["decode_build_s"] > 0
    finally:
        eng.shutdown()


def test_a_stage_is_recorded_and_logged_when_its_body_raises(caplog):
    records = []
    with caplog.at_level("INFO", logger="ray_tpu.serve.llm.stats"):
        with pytest.raises(KeyError):
            with stats.stage(records, "backend"):
                raise KeyError("the backend did not come up")
        with stats.stage(records, "trace", "decode@8"):
            sum(range(1000))
    assert [(r[0], r[1]) for r in records] == [("backend", ""), ("trace", "decode@8")]
    assert [m for m in caplog.messages if m.startswith("setup: ")] == [
        "setup: backend 0.0 s (cpu 0.0)", "setup: trace decode@8 0.0 s (cpu 0.0)",
    ]


def test_a_stage_counts_the_collections_that_fell_inside_it():
    import gc

    stats.listen_for_gc()
    records = []
    with stats.stage(records, "params"):
        gc.collect()  # generation 2
        gc.collect(0)
    with stats.stage(records, "pool"):
        pass
    (params, pool) = records
    assert params[STAGE["gc2"]] == 1 and 0 < params[STAGE["gc_ns"]] <= _wall(params)
    assert pool[STAGE["gc2"]] == 0 and pool[STAGE["gc_ns"]] == 0


def test_a_profile_taken_over_a_stage_holds_it_by_name(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with stats.stage([], "lower", "decode@8"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes if plane.name == "/host:CPU" for line in plane.lines for e in line.events}
    assert "setup.lower decode@8" in names


def test_thread_cpu_reads_every_thread_by_name_and_keeps_the_busiest():
    import threading

    before = stats.thread_cpu_ns()
    assert before and all(isinstance(name, str) and ns >= 0 for name, ns in before.values())
    assert threading.current_thread().name in {name for name, _ in before.values()}
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(2000))

    t = threading.Thread(target=spin, name="a-spinning-thread")
    t.start()
    time.sleep(0.3)
    after = stats.thread_cpu_ns()
    stop.set()
    t.join()
    busiest = stats.busiest_threads(before, after)
    assert 0 < len(busiest) <= 5 and busiest == sorted(busiest, key=lambda b: -b[1])
    assert "a-spinning-thread" in [name for name, _ in busiest]
    assert stats.busiest_threads(after, after) == []
    assert stats.busiest_threads({}, {1: ("a", 5), 2: ("b", 9), 3: ("c", 7)}, n=2) == [["b", 9], ["c", 7]]


# What the installed jax raises on the compiling thread (stats.py's docstring): a hit, a write, neither.
_HIT = ["/jax/compilation_cache/cache_retrieval_time_sec", "/jax/core/compile/backend_compile_duration"]
_WRITE = ["/jax/compilation_cache/cache_misses", "/jax/core/compile/backend_compile_duration"]
_NEITHER = ["/jax/core/compile/backend_compile_duration"]


@pytest.mark.parametrize(
    "events,afresh,retrievals", [(_HIT, 0, 1), (_WRITE, 1, 0), (_NEITHER, 0, 0)], ids=["hit", "write", "neither"],
)
def test_the_listener_tells_a_cache_hit_from_a_compile_that_was_written(events, afresh, retrievals):
    before, held = stats.compile_totals(), stats.COMPILES.n
    for event in events:
        if event.endswith("cache_misses"):
            stats._on_cache_write(event)
        else:
            stats._on_compile_event(event, 0.25, fun_name="jit(a_program)")
    after = stats.compile_totals()
    gained = {k: [after[k][0] - before[k][0], after[k][1] - before[k][1]] for k in after}
    assert gained == {
        "backend_compile": [1, 250_000_000],
        "cache_retrieval": [retrievals, 250_000_000 * retrievals],
        "compiled_afresh": [afresh, 250_000_000 * afresh],
    }
    recs = stats.compile_records()[-(stats.COMPILES.n - held):]
    assert [dict(zip(stats.COMPILE_FIELDS, r))["afresh"] for r in recs if r[2] == "backend_compile"] == [afresh]
    assert all(r[4] == 0 for r in recs if r[2] == "cache_retrieval")
    stats._on_compile_event(_NEITHER[0], 0.1, fun_name="jit(the_next)")  # a write marks ONE compile, its thread's next
    assert stats.compile_records()[-1][4] == 0


def test_the_installed_jax_raises_the_pairs_the_listener_reads(tmp_path):
    """A program compiled with a persistent cache under it: written the first
    time (``afresh``), read the second (a retrieval, then a ``backend_compile``
    that is not afresh); in another process, since jax fixes its cache on first use."""
    import subprocess
    import sys

    code = """
import json, sys
import jax, jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from ray_tpu.serve.llm import stats
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
stats.listen_for_compiles()
def a_program_worth_keeping(x):
    return jnp.tanh(x @ x) + 1
out = []
for _ in range(2):
    n = stats.COMPILES.n
    jax.jit(a_program_worth_keeping)(jnp.ones((8, 8))).block_until_ready()
    recs = [r[2:] for r in stats.compile_records()[-(stats.COMPILES.n - n):]]
    at = [r[1] for r in recs].index("jit(a_program_worth_keeping)")
    out.append(recs[max(0, at - 1):at + 1])  # the program's own record and the one raised just before it
    jax.clear_caches()
    compilation_cache.reset_cache()
print(json.dumps([out, stats.compile_totals()]))
"""
    import os

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    done = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)], env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    (cold, warm), totals = json.loads(done.stdout.splitlines()[-1])
    assert cold[-1] == ["backend_compile", "jit(a_program_worth_keeping)", 1] and cold[0][0] == "backend_compile"
    assert warm == [["cache_retrieval", "", 0], ["backend_compile", "jit(a_program_worth_keeping)", 0]]
    assert totals["cache_retrieval"][0] >= 1 and totals["compiled_afresh"][0] >= 1


def test_a_build_called_with_room_meets_no_chunk_boundary_of_the_data_stack():
    """What moved ``decode_build_s`` by seconds with a key more in a dict literal (PERF.md, PR 49 and 52): a call
    that starts a chunk of the thread's data stack maps and frees the chunk every time. Found here by scanning
    depths for the one where a loop of calls runs tens of times longer; from ``_with_room`` it does not."""
    import sys

    from ray_tpu.serve.llm.engine import _with_room

    assert _with_room.__code__.co_stacksize == 40_000 and _with_room(lambda: 7) == 7

    def leaf(x):
        return x + 1

    def loop():
        t0 = time.perf_counter()
        for i in range(4000):
            leaf(i)
        return time.perf_counter() - t0

    def at_depth(depth, run):
        return run() if depth == 0 else at_depth(depth - 1, run)

    found = {}

    def scan():  # on a thread of its own: its data stack starts empty, whatever called this test
        plain = [min(at_depth(d, loop) for _ in range(2)) for d in range(600)]
        worst = max(range(600), key=plain.__getitem__)
        found.update(usual=sorted(plain)[len(plain) // 2], worst=worst, slow=plain[worst])
        found["roomy"] = min(at_depth(worst, lambda: _with_room(loop)) for _ in range(5))

    import threading

    thread = threading.Thread(target=scan)
    thread.start()
    thread.join()
    if found["slow"] < 15 * found["usual"]:
        pytest.skip(f"no boundary found under 600 frames on {sys.version.split()[0]}: {found}")
    assert found["roomy"] < found["slow"] / 5 and found["roomy"] < 8 * found["usual"], found


def test_metrics_fold_observes_ttft_once_per_request_that_ended(model):
    from ray_tpu._private import self_metrics

    inst = self_metrics.instruments()

    def count(key):
        return sum(inst[key]._totals.values())

    def series(key):
        return {k: v for k, v in inst[key]._values.items()}

    self_metrics._collect_serve_llm_stats()  # whatever earlier tests left
    ttft0, tpot0 = count("serve_llm_ttft"), count("serve_llm_tpot")
    loop0 = sum(series("serve_llm_loop_seconds").values())
    iters0 = sum(series("serve_llm_iterations").values())
    eng = _engine(model)
    try:
        reqs = [eng.submit(_prompt(80 + i, 5), max_new_tokens=4) for i in range(3)]
        for r in reqs:
            r.result(timeout=60)
        one = eng.submit(_prompt(90, 5), max_new_tokens=1)  # one token: a TTFT, no TPOT
        one.result(timeout=60)
    finally:
        eng.shutdown()
    n_iterations = sum(eng.stats()["iterations"].values())
    self_metrics._collect_serve_llm_stats()  # the engine is gone, its recorder is not
    self_metrics._collect_serve_llm_stats()  # a second flush finds nothing new
    assert count("serve_llm_ttft") - ttft0 == 4
    assert count("serve_llm_tpot") - tpot0 == 3
    assert sum(series("serve_llm_iterations").values()) - iters0 == n_iterations
    assert sum(series("serve_llm_loop_seconds").values()) > loop0


# ---------------------------------------------------------------------------
# through the proxy (one small cluster)
# ---------------------------------------------------------------------------


def test_the_proxy_stamps_a_request_and_forwards_its_identifier():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        serve.start()
        app = serve.deployment(LLMDeployment).bind(MODEL, engine_config=ENGINE)
        handle = serve.run(app, route_prefix="/llm")
        host, port = serve.http_address()
        for headers in ({"x-request-id": "from-the-client"}, {}):
            t0 = time.monotonic_ns()
            req = urllib.request.Request(
                f"http://{host}:{port}/llm",
                data=json.dumps({"tokens": _prompt(95, 6), "max_new_tokens": 3, "stream": False}).encode(),
                headers=headers,
            )
            assert len(json.loads(urllib.request.urlopen(req, timeout=120).read())["tokens"]) == 3
        spans = ray_tpu.get(handle.get_stats.remote(), timeout=60)["spans"]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    recs = [dict(zip(spans["fields"]["requests"], r)) for r in spans["requests"]]
    assert len(recs) == 2
    assert recs[0]["request_id"] == "from-the-client"
    assert len(recs[1]["request_id"]) == 16 and recs[1]["request_id"] != recs[0]["request_id"]
    for rec in recs:  # the proxy's stamp is on the replica's clock: one host
        assert 0 < rec["t_recv_ns"] <= rec["t_submit_ns"] <= rec["t_first_ns"] <= rec["t_done_ns"]
    assert recs[1]["t_recv_ns"] >= t0


def test_a_replica_stamps_its_own_way_from_the_controllers_decision_to_its_first_answer():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        serve.start()
        app = serve.deployment(LLMDeployment).bind(MODEL, engine_config=ENGINE)
        t0 = time.monotonic_ns()
        handle = serve.run(app, route_prefix="/llm")
        t1 = time.monotonic_ns()
        spans = ray_tpu.get(handle.get_stats.remote(), timeout=60)["spans"]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    stamps = spans["setup_stamps"]
    assert list(stamps) == list(SETUP_STAMPS)  # in the order taken
    order = [stamps[k] for k in ("t_requested_ns", "t_process_ns", "t_actor_ns", "t_callable_ns", "t_ready_ns")]
    assert all(isinstance(t, int) for t in order) and t0 <= order[0] and order[-1] <= t1
    assert order == sorted(order)
    # The deployment's first stage starts behind the replica's last stamp before it, and the engine is built
    # before the first health check answers: the stages lie between the two.
    stages = spans["stages"]
    assert stamps["t_callable_ns"] <= stages[0][3] and max(r[4] for r in stages) <= stamps["t_ready_ns"]
    assert stamps != SETUP_STAMPS  # the replica's process has them, not this one
