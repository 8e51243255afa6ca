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
    assert rec["t_admit_ns"] == int(req.t_admit * 1e9)  # the FIRST admission's
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
    assert set(spans) == {"iterations", "requests", "compiles", "deliveries", "gc", "gc_younger", "setup", "fields"}
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
