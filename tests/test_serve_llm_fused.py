"""A pass reads the weights once (PR 40): where a pass of ``LLMEngine`` has a
prefill chunk AND rows that decode, and the engine's shape fuses, the chunk
rides inside the decode step's program (``_compiled_fns``'
``decode_with_chunk``; ``generate.paged_decode_step_with_chunk``). Held here:
the streams are those of each request alone, the pool holds what the two
programs would have written, the rule that decides which engines fuse, a
prompt's last chunk inside a step, cancels and preemptions while such a step is
in flight, run-ahead, and that every program is built from shapes before the
constructor returns."""

import numpy as np
import pytest

from ray_tpu.serve.llm import stats

MODEL = dict(
    vocab_size=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=128,
    sliding_window=40, dtype="float32", remat=False,
)
IT = {name: i for i, name in enumerate(stats.ITERATION_FIELDS)}
SAMPLINGS = pytest.mark.parametrize(
    "sampling",
    [dict(temperature=0.0), dict(temperature=0.9, top_k=16), dict(temperature=1.0)],
    ids=["greedy", "sampled_top_k", "sampled"],
)


def _config(**over):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    kw = dict(MODEL, **over)
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    return TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    """Mistral's shape in small: grouped KV heads, one sliding window for
    the whole model, float32."""
    import jax

    from ray_tpu.models.transformer import init_params

    cfg = _config()
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, MODEL["vocab_size"], n).tolist()


def _stopped(model, **kw):
    """An engine whose scheduler thread has exited, re-opened for submits: the
    test is the loop (``_pass``)."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, **dict(dict(num_slots=3, block_size=4, max_model_len=96, prefill_chunk=8), **kw))
    eng.shutdown()
    eng._crashed = None
    return eng


def _pass(eng):
    """One pass of ``_loop``, by hand."""
    eng._sweep_cancelled()
    eng._admit()
    eng._ticks()


def _drive(eng, reqs, passes=600):
    for _ in range(passes):
        if all(r._finished for r in reqs):
            break
        _pass(eng)
    assert all(r._finished for r in reqs) and eng._inflight is None
    s = eng.stats()
    assert s["free_blocks"] + s["cached_blocks"] == s["num_blocks"], s  # no block leaked
    assert s["kv_pool_not_donated"] == 0 and s["host_logit_rows"] == 0
    return s


def _alone(model, prompt, n, **sampling):
    """The stream of one request with the engine to itself: no pass of it has
    both a chunk and a decode row."""
    eng = _stopped(model)
    req = eng.submit(prompt, max_new_tokens=n, **sampling)
    s = _drive(eng, [req])
    assert s["decode_steps_with_chunk"] == 0
    return req.result(5)


def _keep_rows_at_release(eng, kept: dict):
    """``kept[rid]``: the keys and values a request leaves in its blocks, read
    through its table as it gives them back, [2, L, tokens fed, KV, Dh]."""
    release = eng._release_blocks

    def keep_then_release(req):
        fed = len(req.prompt) + len(req._sched_generated) - 1
        if req._sched_table and fed > 0 and not req.cancelled.is_set():
            table = np.asarray(req._sched_table)
            rows = [np.asarray(eng._cache[name])[:, table] for name in ("k", "v")]
            kept[req.id] = np.stack([r.reshape(r.shape[0], -1, *r.shape[3:])[:, :fed] for r in rows])
        release(req)

    eng._release_blocks = keep_then_release


SPECS = [(41, 30, 9), (42, 13, 14), (43, 21, 6), (44, 5, 11), (45, 17, 8)]  # (prompt's seed, its length, new tokens)


@SAMPLINGS
def test_streams_submitted_together_are_each_what_it_is_alone(model, sampling):
    """Five requests on three slots, so prompts prefill while rows decode and
    late ones are admitted mid-stream: token for token each stream is what
    the request gives alone (the draw is keyed by seed and position), and some
    decode steps carried a chunk."""
    eng = _stopped(model)
    reqs = [
        eng.submit(_prompt(seed, n), max_new_tokens=new, **(dict(sampling, seed=seed) if sampling["temperature"] else sampling))
        for seed, n, new in SPECS
    ]
    s = _drive(eng, reqs)
    assert 0 < s["decode_steps_with_chunk"] <= s["decode_steps"]
    assert s["decode_rows_dropped"] == 0 and s["preemptions"] == 0
    for req, (seed, n, new) in zip(reqs, SPECS):
        alone = _alone(model, _prompt(seed, n), new, **(dict(sampling, seed=seed) if sampling["temperature"] else sampling))
        assert req.result(5) == alone and len(alone) == new


def test_twelve_plain_heads_cached_as_sixteen_serve_the_full_forward_passes_tokens():
    """Plain multi-head attention over 12 heads: a cached row holds 16, four
    of them zeros (``generate._cache_heads``), queries get zero heads to match,
    a decode row is padded to eight query rows, the softmax is normalised after
    the weighted sum and the zero heads' outputs are dropped (PR 41: one
    formulation for every plain multi-head model). Through the step that carries
    a chunk and through the two programs alike, every greedy token is the full
    forward pass's (training's attention: no cache, no padding, ``softmax``),
    or lies within float32 rounding of its best."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward, init_params

    generate = importlib.import_module("ray_tpu.models.generate")
    cfg = _config(n_heads=12, n_kv_heads=12, sliding_window=0)
    assert generate._cache_heads(cfg) == 16 and generate._cache_rows(cfg)["k"] == (16, 4)
    params = init_params(jax.random.PRNGKey(3), cfg)
    logits = jax.jit(lambda tokens: forward(params, tokens, cfg)[0])
    specs = [(51, 30, 7), (52, 13, 9), (53, 21, 6), (54, 5, 8)]
    for fuse in (True, False):
        eng = _stopped((params, cfg))
        if not fuse:
            eng._fuses = False
        reqs = [eng.submit(_prompt(seed, n), max_new_tokens=new) for seed, n, new in specs]
        s = _drive(eng, reqs)
        assert (s["decode_steps_with_chunk"] > 0) == fuse
        for req in reqs:
            seq = req.prompt + req.result(5)
            want = np.asarray(logits(jnp.asarray([seq])))[0, len(req.prompt) - 1 : -1]
            assert (want.max(axis=-1) - want[np.arange(len(want)), seq[len(req.prompt) :]]).max() < 1e-4


def test_the_pool_holds_what_the_two_programs_would_have_written(model):
    """The same five requests through an engine that fuses and one held to
    the two programs: what each request leaves in its blocks, read through its
    table as it gives them back, agrees to 1e-5 (and the tokens exactly)."""
    kept, streams = {}, {}
    for fuses in (True, False):
        eng = _stopped(model)
        assert eng._fuses
        if not fuses:
            eng._fuses = False
        kept[fuses] = {}
        _keep_rows_at_release(eng, kept[fuses])
        reqs = [eng.submit(_prompt(seed, n), max_new_tokens=new) for seed, n, new in SPECS]
        s = _drive(eng, reqs)
        assert (s["decode_steps_with_chunk"] > 0) == fuses
        streams[fuses] = [r.result(5) for r in reqs]
    assert streams[True] == streams[False]
    assert set(kept[True]) == set(kept[False]) and len(kept[True]) == len(SPECS)
    for rid, rows in kept[True].items():
        assert rows.shape == kept[False][rid].shape and rows.shape[2] > 0
        np.testing.assert_allclose(rows, kept[False][rid], atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# which engines fuse
# ---------------------------------------------------------------------------

LATENT = dict(
    n_kv_heads=4, sliding_window=0, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
    v_head_dim=20,
)
PATTERN = dict(n_layers=4, sliding_window=24, layer_kinds=("window", "window", "window", "full"))
EXPERTS = dict(
    n_layers=3, num_experts=8, experts_per_token=2, d_expert=32, num_shared_experts=1, first_dense_layers=1,
    sliding_window=0,
)


@pytest.mark.parametrize(
    "over, engine, fuses",
    [
        ({}, dict(num_slots=96, prefill_chunk=32), True),  # 128 rows: one tile
        ({}, dict(num_slots=97, prefill_chunk=32), False),  # 129
        ({}, dict(num_slots=3, prefill_chunk=8, role="prefill"), False),  # never decodes
        (LATENT, dict(num_slots=3, prefill_chunk=8), False),
        (PATTERN, dict(num_slots=3, prefill_chunk=8), False),
        (EXPERTS, dict(num_slots=3, prefill_chunk=8), False),
    ],
    ids=["128_rows", "129_rows", "prefill_role", "latent_pool", "layer_pattern", "routed_experts"],
)
def test_an_engine_fuses_by_its_shape_and_by_what_its_pool_holds(over, engine, fuses):
    """``_shape_fuses``: rows + chunk within one tile of the matrix unit, one
    group of key and value leaves, an engine that decodes. One that does not
    fuse builds no step with a chunk and, under traffic that has chunks and
    rows in one pass, dispatches only the two programs; either way the
    streams are what ``generate`` computes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import generate
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = _config(**over)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(params, cfg, block_size=4, max_model_len=64, **engine)
    try:
        assert eng._fuses == fuses
        if engine.get("role") == "prefill":
            return
        prompts = [_prompt(60 + i, n) for i, n in enumerate((19, 7, 26, 12))]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert r.result(120) == generate(params, jnp.asarray([p]), cfg, max_new_tokens=10)[0].tolist()
        s = eng.stats()
        assert s["iterations"]["mixed"] > 0 or fuses
        assert (s["decode_steps_with_chunk"] > 0) == fuses
        chunked = [r[IT["chunk_tokens"]] for r in eng.spans.iterations.since()]
        assert (max(chunked) > 0) == fuses and all(c in (0, r[IT["prefill_tokens"]]) for c, r in zip(chunked, eng.spans.iterations.since()))
    finally:
        eng.shutdown()


def test_the_step_with_a_chunk_refuses_what_it_does_not_carry():
    import jax.numpy as jnp

    from ray_tpu.models.generate import paged_decode_step_with_chunk

    for over in (LATENT, PATTERN, EXPERTS):
        with pytest.raises(NotImplementedError, match="one group of key and value leaves"):
            paged_decode_step_with_chunk({}, jnp.zeros(2, jnp.int32), None, {}, None, None, None, None, None, _config(**over))


# ---------------------------------------------------------------------------
# a prompt's last chunk inside a step
# ---------------------------------------------------------------------------


def _until_decoding(eng, req, tokens=3):
    while len(req._sched_generated) < tokens:
        _pass(eng)


def test_a_last_chunk_inside_a_step_emits_its_token_once_and_registers_its_blocks(model):
    """One row decodes; a 21-token prompt prefills beside it in three chunks,
    each inside a step. The last makes the request one of its step's: the
    first token lands with that step's ids (no ``llm.prefill.fetch``), once,
    its blocks are in the prefix cache from the dispatch on, and a second
    prompt with the same first 20 tokens takes them."""
    eng = _stopped(model)
    runner = eng.submit(_prompt(71, 6), max_new_tokens=40)
    _until_decoding(eng, runner)
    shared = _prompt(72, 21)
    late = eng.submit(shared, max_new_tokens=5)
    before = eng.stats()
    while late._sched_state != "decode":
        _pass(eng)
    step = eng._inflight
    # Dispatched, not landed: nothing emitted yet, the blocks registered already.
    assert late in step.reqs and step.reqs[-1] is late and step.rows == len(step.reqs) - 1
    assert late._sched_generated == [] and late.t_first is None
    assert late._sched_pos == len(shared) - 1  # its last token's row is in the step
    assert len(late._sched_registered_bids) == 20 // eng.block_size
    _pass(eng)
    assert len(late._sched_generated) == 1 and late.t_first is not None and late._sched_pos == len(shared)
    again = eng.submit(shared[:20] + _prompt(73, 4), max_new_tokens=3)
    s = _drive(eng, [runner, late, again])
    assert again.cached_tokens == 20 and s["prefix_hit_blocks"] - before["prefix_hit_blocks"] == 5
    assert s["decode_steps_with_chunk"] - before["decode_steps_with_chunk"] >= 3
    assert s["span_ns"]["llm.prefill.fetch"] == before["span_ns"]["llm.prefill.fetch"]  # no chunk fetched alone
    assert s["decode_rows_dropped"] == 0
    for req, (p, n) in ((runner, (_prompt(71, 6), 40)), (late, (shared, 5)), (again, (again.prompt, 3))):
        got = req.result(5)
        assert got == _alone(model, p, n) and len(got) == n  # every token once


def test_a_one_token_request_ends_with_the_step_that_carried_its_last_chunk(model):
    eng = _stopped(model)
    runner = eng.submit(_prompt(74, 6), max_new_tokens=30)
    _until_decoding(eng, runner)
    one = eng.submit(_prompt(75, 7), max_new_tokens=1)
    s = _drive(eng, [runner, one])
    assert s["decode_steps_with_chunk"] == 1 and one.result(5) == _alone(model, _prompt(75, 7), 1)
    assert runner.result(5) == _alone(model, _prompt(74, 6), 30)


# ---------------------------------------------------------------------------
# cancel and preemption while a step with a chunk is in flight
# ---------------------------------------------------------------------------


def _with_a_chunk_in_flight(model, last: bool, **kw):
    """(engine, the decoding request, the prefilling one, the step in flight
    that carries a chunk of it: its last if ``last``)."""
    eng = _stopped(model, **kw)
    runner = eng.submit(_prompt(81, 6), max_new_tokens=40)
    _until_decoding(eng, runner)
    late = eng.submit(_prompt(82, 21), max_new_tokens=6)
    steps = eng.stats()["decode_steps_with_chunk"]
    while eng.stats()["decode_steps_with_chunk"] == steps or (last and late._sched_state != "decode"):
        _pass(eng)
    assert eng._inflight is not None and (late in eng._inflight.reqs) == last
    return eng, runner, late, eng._inflight


@pytest.mark.parametrize("last", [False, True], ids=["a_middle_chunk", "the_last_chunk"])
@pytest.mark.parametrize("who", ["the_prefilling_request", "the_decoding_row"])
def test_a_cancel_while_a_step_with_a_chunk_is_in_flight(model, who, last):
    """The sweep frees the cancelled request's blocks at once, the step still
    in flight; an id the step drew for it (the decoding row's; the first token
    of a last chunk) is dropped at the fetch and counted, never emitted; the
    other request's stream is what it is alone and every block comes back."""
    eng, runner, late, step = _with_a_chunk_in_flight(model, last)
    doomed, survivor = (late, runner) if who == "the_prefilling_request" else (runner, late)
    emitted = list(doomed._sched_generated)
    eng.cancel(doomed)
    eng._sweep_cancelled()
    assert doomed._finished and eng._inflight is step
    s = _drive(eng, [survivor])
    assert s["decode_rows_dropped"] == (doomed in step.reqs) and s["cancelled"] == 1
    assert list(doomed) == emitted == doomed._sched_generated
    want = (_prompt(81, 6), 40) if survivor is runner else (_prompt(82, 21), 6)
    assert survivor.result(5) == _alone(model, *want)


@pytest.mark.parametrize("last", [False, True], ids=["a_middle_chunk", "the_last_chunk"])
def test_a_preemption_while_a_step_with_a_chunk_is_in_flight(model, last):
    """The youngest request is preempted with its chunk in flight, where a
    dry pool would do it: while the next step is built, ahead of that one's
    landing. An id the step drew for it is dropped, a chunk of it that was to
    ride the step being built stays out, it prefills again from its first
    token and draws the same tokens at the same indices; both streams are
    what they are alone."""
    eng, runner, late, step = _with_a_chunk_in_flight(model, last)
    launch = eng._launch_step

    def launch_on_a_dry_pool(ahead_of, chunk=None):
        if not late.preemptions:
            assert ahead_of is step and (chunk is late) == (not last)
            eng._preempt(late)
            assert late._sched_state == "waiting" and late._sched_slot is None
        return launch(ahead_of, chunk)

    eng._launch_step = launch_on_a_dry_pool
    s = _drive(eng, [runner, late])
    assert s["preemptions"] == 1 and late.preemptions == 1
    assert s["decode_rows_dropped"] == int(last)
    assert runner.result(5) == _alone(model, _prompt(81, 6), 40)
    assert late.result(5) == _alone(model, _prompt(82, 21), 6)


def test_a_dry_pool_preempts_the_prefilling_request_out_of_the_step_being_built(model):
    """A pool of 12 blocks: the decoding row needs its next block while the
    younger request prefills, so building the step preempts that request, and
    the step goes without a chunk (the plain decode program). Readmitted once
    the row has finished, its stream is still what it is alone."""
    eng = _stopped(model, num_slots=2, num_blocks=13)
    runner = eng.submit(_prompt(83, 7), max_new_tokens=26)  # grows to 33 tokens: 9 blocks
    _until_decoding(eng, runner, 2)
    late = eng.submit(_prompt(84, 30), max_new_tokens=4)  # 8 blocks at admission
    s = _drive(eng, [runner, late])
    assert s["preemptions"] >= 1 and late.preemptions >= 1 and runner.preemptions == 0
    assert runner.result(5) == _alone(model, _prompt(83, 7), 26)
    assert late.result(5) == _alone(model, _prompt(84, 30), 4)


@pytest.mark.parametrize("runner_at", [0, 70], ids=["a_row_short_of_64_tokens", "a_row_past_them"])
def test_chunks_ride_the_steps_however_long_the_decoding_row(model, runner_at):
    """The step with a chunk is built once, at the whole table (24 blocks of
    this engine's 16 / 24), so a prompt's chunks ride the steps of a decoding
    row whether it is short of the narrower rung's 64 tokens or past them: no
    chunk runs as a program of its own while a step is in flight, and the
    streams are what they are alone either way."""
    eng = _stopped(model)
    assert eng._view_rungs == (16, 24) and eng._fuses
    prefill, alone = eng._prefill_fn, []
    eng._prefill_fn = lambda *a: (alone.append(eng._inflight is not None), prefill(*a))[1]
    runner = eng.submit(_prompt(85, 40), max_new_tokens=50)  # 40 -> 90 tokens: crosses 64
    _until_decoding(eng, runner)
    ran_alone = len(alone)  # the runner's own chunks: nothing decoded beside them
    assert ran_alone == 5 and not any(alone)
    while runner._sched_pos < runner_at:
        _pass(eng)
    assert (runner._sched_pos < 64) == (runner_at == 0) and eng.stats()["decode_steps_with_chunk"] == 0
    late = [eng.submit(_prompt(seed, 20), max_new_tokens=4) for seed in (86, 87)]
    done = _drive(eng, [runner, *late])
    assert done["decode_steps_with_chunk"] == 6 and len(alone) == ran_alone  # 20 tokens: three chunks each, all inside steps
    assert done["decode_width_steps"][24] >= 6  # handed the whole table, whatever the rows hold
    for req, (seed, n, new) in ((runner, (85, 40, 50)), (late[0], (86, 20, 4)), (late[1], (87, 20, 4))):
        assert req.result(5) == _alone(model, _prompt(seed, n), new)


# ---------------------------------------------------------------------------
# run-ahead
# ---------------------------------------------------------------------------


def test_a_step_with_a_chunk_is_dispatched_on_the_ids_of_the_step_before(model):
    """Every step with a chunk but one launched into an empty pipeline goes
    out while its predecessor is unfetched: its carried rows say
    ``_ID_IN_FLIGHT`` and its ``ids`` argument IS the predecessor's output,
    still on the device; the row of a request whose LAST chunk rode the
    predecessor feeds from the device too, and the slot of a last chunk says
    ``_ID_FROM_CHUNK``."""
    from ray_tpu.serve.llm.engine import _ID_FROM_CHUNK, _ID_IN_FLIGHT, _ROW_TOKEN

    eng = _stopped(model)
    fused, seen = eng._fused_fn, []

    def spy(p, rows, c, ids, tokens, chunk_rows):
        seen.append((np.asarray(rows)[:, _ROW_TOKEN].tolist(), ids, eng._inflight))
        return fused(p, rows, c, ids, tokens, chunk_rows)

    eng._fused_fn = spy
    launch, behind = eng._launch_step, []
    eng._launch_step = lambda ahead_of, chunk=None: (behind.append(ahead_of), launch(ahead_of, chunk))[1]
    reqs = [eng.submit(_prompt(seed, n), max_new_tokens=new) for seed, n, new in SPECS]
    s = _drive(eng, reqs)
    assert len(seen) == s["decode_steps_with_chunk"] > 3
    assert s["decode_steps_run_ahead"] >= s["decode_steps"] - 2
    from_chunk = 0
    for column, ids, _ in seen:
        ahead_of = next(a for a in reversed(behind) if a is None or a.ids is ids)
        assert ahead_of is not None and ids is ahead_of.ids  # the predecessor's ids, unfetched
        carried = [column[slot] for slot in ahead_of.slots]
        assert carried and all(c in (_ID_IN_FLIGHT, 0) for c in carried) and _ID_IN_FLIGHT in carried
        from_chunk += column.count(_ID_FROM_CHUNK)
    assert from_chunk >= 3  # SPECS' later prompts end inside a step
    for req, (seed, n, new) in zip(reqs, SPECS):
        assert req.result(5) == _alone(model, _prompt(seed, n), new)


# ---------------------------------------------------------------------------
# the programs are built from shapes, all before the constructor returns
# ---------------------------------------------------------------------------


def _backend_compiles(since):
    return [r[3] for r in stats.COMPILES.since(since) if r[2] == "backend_compile"]


@pytest.mark.parametrize(
    "over, fuses", [(dict(d_ff=88), True), (dict(d_ff=104, **PATTERN), False)], ids=["a_shape_that_fuses", "one_that_does_not"]
)
def test_an_engine_that_fuses_builds_every_program_before_its_constructor_returns(over, fuses):
    """An engine whose shape fuses builds, before its scheduler starts, the
    decode step at every rung, the step with a chunk (one, at the whole
    table) and the prefill program, and a mixed run across every rung builds
    nothing more. One that does not keeps the start it had: the decode step at
    every rung, and its first request builds the prefill program."""
    import jax

    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm import LLMEngine

    # Configurations no other test uses: their programs are in no cache yet.
    cfg = _config(max_seq_len=256, **over)
    stats.listen_for_compiles()
    since = stats.COMPILES.n
    eng = LLMEngine(
        init_params(jax.random.PRNGKey(0), cfg), cfg, num_slots=2, block_size=4, max_model_len=256, prefill_chunk=4
    )
    try:
        assert eng._view_rungs == (16, 32, 64) and eng._fuses == fuses
        built = [name for name in _backend_compiles(since) if "lambda" in name or "prefill_chunk_row" in name]
        assert len(built) == (5 if fuses else 3) and ("jit(prefill_chunk_row)" in built) == fuses, built
        setup = eng.spans.setup
        assert setup["decode_build_s"] > 0 and (setup.get("fused_build_s", 0) > 0) == fuses
        assert eng.stats()["decode_steps"] == 0 and eng.spans.export()["iterations"] == []
        ready = stats.COMPILES.n
        long_ = eng.submit(_prompt(91, 50), max_new_tokens=200)  # crosses 64 and 128 tokens: every rung
        others = [eng.submit(_prompt(92 + i, 9 + 30 * i), max_new_tokens=12) for i in range(4)]
        for r in [long_, *others]:
            r.result(120)
        s = eng.stats()
        assert set(s["decode_width_steps"]) == {16, 32, 64} and min(s["decode_width_steps"].values()) > 0
        assert (s["decode_steps_with_chunk"] > 0) == fuses and s["iterations"]["prefill"] > 0
        assert [name for name in _backend_compiles(ready) if "lambda" in name] == []  # no decode step, ever
        assert ("jit(prefill_chunk_row)" in _backend_compiles(ready)) == (not fuses)
    finally:
        eng.shutdown()
