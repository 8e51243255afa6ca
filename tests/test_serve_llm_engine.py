"""Continuous-batching LLM serving (ISSUE 11): engine scheduler, prefix
cache, preemption, stream hygiene, cache-aware routing, end-to-end SSE.

Layout (mindful of the tier-1 budget): engine/replica/router tests run with
NO cluster (one shared tiny model, compiled programs shared through the
engine's process-level jit cache); the end-to-end HTTP tests share ONE
module-scoped cluster; the concurrency sweep is marked `slow`.
"""

import functools
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

MODEL = dict(
    vocab_size=128,
    d_model=48,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    max_seq_len=64,
    dtype="float32",
    remat=False,
)


def _cfg():
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    kw = dict(MODEL)
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    return TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models.transformer import init_params

    cfg = _cfg()
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _dense(params, cfg, prompt, n):
    import jax.numpy as jnp

    from ray_tpu.models.generate import generate

    return np.asarray(
        generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                 max_new_tokens=n, temperature=0.0)
    )[0].tolist()


@functools.lru_cache(maxsize=None)
def _oracle_fns(cfg):
    """``generate()``'s two halves, the dense-cache prefill and decode step,
    each followed by the serving programs' draw."""
    import jax

    from ray_tpu.models.generate import decode_step, draw_tokens, prefill

    def first(params, tokens, cache, draw):
        logits, cache, _ = prefill(params, tokens, cache, cfg)
        return draw_tokens(logits, *draw), cache

    def then(params, token, cache, pos, draw):
        logits, cache = decode_step(params, token, cache, pos, cfg)
        return draw_tokens(logits, *draw), cache

    return jax.jit(first), jax.jit(then)


def _oracle(params, cfg, prompt, n, temperature=0.0, top_k=0, seed=0):
    """The stream of one request alone, by the dense cache: ``generate()``'s
    for a greedy request, and for a sampled one ``generate()``'s prefill and
    decode step with ``draw_tokens`` keyed by (seed, index of the token) in
    place of its carried key."""
    if not temperature:
        return _dense(params, cfg, prompt, n)
    import jax.numpy as jnp

    from ray_tpu.models.generate import init_cache

    first, then = _oracle_fns(cfg)
    halves = jnp.asarray([[seed & 0xFFFFFFFF, seed >> 32]], jnp.uint32)

    def draw(i):
        return (jnp.full((1,), temperature, jnp.float32), jnp.full((1,), top_k, jnp.int32),
                halves, jnp.full((1,), i, jnp.int32))

    cache = init_cache(cfg, 1, len(prompt) + n)
    tok, cache = first(params, jnp.asarray([prompt], jnp.int32), cache, draw(0))
    out = [int(tok[0])]
    for i in range(1, n):
        tok, cache = then(params, tok, cache, jnp.int32(len(prompt) + i - 1), draw(i))
        out.append(int(tok[0]))
    return out


SAMPLINGS = pytest.mark.parametrize(
    "sampling",
    [dict(temperature=0.0), dict(temperature=0.9, top_k=16, seed=7), dict(temperature=1.0, seed=11)],
    ids=["greedy", "sampled_top_k", "sampled"],
)


def _rand_prompt(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


# ---------------------------------------------------------------------------
# engine (no cluster)
# ---------------------------------------------------------------------------


def test_continuous_schedule_matches_dense_generate(model):
    """THE acceptance oracle: greedy tokens across a multi-sequence schedule
    with MID-STREAM admissions are exactly the dense-cache generate()
    output per request — paged attention + slot scheduling are invisible."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=3, block_size=4,
                    max_model_len=32, prefill_chunk=4)
    try:
        prompts = [_rand_prompt(i + 1, 7) for i in range(5)]
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts[:3]]
        # Wait until decode is underway, then admit two more mid-stream.
        # (result() continues from the already-consumed first token.)
        firsts = [next(iter(r)) for r in reqs]
        reqs2 = [eng.submit(p, max_new_tokens=6) for p in prompts[3:]]
        outs = [[f] + r.result(timeout=120) for f, r in zip(firsts, reqs)]
        outs += [r.result(timeout=120) for r in reqs2]
        for p, o in zip(prompts, outs):
            assert o == _dense(params, cfg, p, 6)
        assert eng.stats()["admitted"] == 5
    finally:
        eng.shutdown()


def test_prefix_cache_reuse_refcounts_and_hint(model):
    """Admissions sharing a system prompt reuse its KV blocks (hit counters,
    fewer allocations), tokens still match the oracle, and refs return to 0
    so the blocks stay cached for the NEXT admission."""
    from ray_tpu.serve.llm import LLMEngine, prefix_route_hint

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4,
                    max_model_len=32, prefill_chunk=4)
    try:
        system = [5, 9, 3, 7, 1, 2, 8, 4]  # two full blocks
        p1, p2 = system + [11, 13], system + [17]
        assert prefix_route_hint(p1, 4) == prefix_route_hint(p2, 4) != ""
        o1 = eng.submit(p1, max_new_tokens=4).result(60)
        o2 = eng.submit(p2, max_new_tokens=4).result(60)
        s = eng.stats()
        assert s["prefix_hit_blocks"] == 2, s
        assert o1 == _dense(params, cfg, p1, 4)
        assert o2 == _dense(params, cfg, p2, 4)
        # Shared blocks are cached with refs 0 — a third request hits again.
        assert all(e.refs == 0 for e in eng._prefix.values())
        eng.submit(system + [19], max_new_tokens=3).result(60)
        assert eng.stats()["prefix_hit_blocks"] == 4
    finally:
        eng.shutdown()


def test_preemption_recompute_matches_oracle(model):
    """An undersized pool forces preemption mid-decode; the preempted
    sequence re-admits with its emitted tokens teacher-forced — final
    tokens for BOTH sequences still match the dense oracle exactly."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4,
                    max_model_len=40, num_blocks=13, prefill_chunk=4)
    try:
        pa, pb = [3] * 6, [9] * 6
        ra = eng.submit(pa, max_new_tokens=20)
        rb = eng.submit(pb, max_new_tokens=20)
        oa, ob = ra.result(120), rb.result(120)
        s = eng.stats()
        assert s["preemptions"] >= 1, s
        assert oa == _dense(params, cfg, pa, 20)
        assert ob == _dense(params, cfg, pb, 20)
        # No leak: every pool block is free or parked in the prefix cache.
        assert s["free_blocks"] + s["cached_blocks"] == s["num_blocks"]
    finally:
        eng.shutdown()


def test_prefix_eviction_under_pressure(model):
    """refs-0 cached prefix blocks are evicted LRU when the free list runs
    dry, instead of blocking admission forever."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    # 5 usable blocks; each 9-token request needs 3 — by the third
    # admission the free list is dry and refs-0 cached prefixes must go.
    eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                    max_model_len=24, num_blocks=6, prefill_chunk=4)
    try:
        eng.submit(_rand_prompt(7, 9), max_new_tokens=4).result(60)
        assert eng.stats()["cached_blocks"] == 2
        eng.submit(_rand_prompt(8, 9), max_new_tokens=4).result(60)
        eng.submit(_rand_prompt(9, 9), max_new_tokens=4).result(60)
        s = eng.stats()
        assert s["evicted_blocks"] >= 1, s
        assert s["free_blocks"] + s["cached_blocks"] == s["num_blocks"]
    finally:
        eng.shutdown()


def _stopped_engine(model, **kw):
    """White-box: an engine whose scheduler thread has exited (idle, so the
    loop's exit sweep had nothing to finalize), re-opened for submits so a
    test can drive ``_admit`` and the two ticks by hand, deterministically."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, block_size=4, prefill_chunk=4, **kw)
    eng.shutdown()
    eng._crashed = None
    return eng


def test_admission_does_not_double_count_cached_hits_as_evictable(model):
    """Regression: with the free list EMPTY and the only refs-0 cached
    blocks being the request's own prefix hits, admission must wait — not
    count those blocks as evictable supply, take refs on them, and then die
    on an empty alloc loop (which killed the scheduler thread engine-wide).

    The race state (every non-hit block held by running sequences) is built
    by hand with the scheduler thread STOPPED, and _admit() driven directly
    — the only deterministic way to pin this admission-time invariant."""
    from ray_tpu.serve.llm import block_hashes
    from ray_tpu.serve.llm.engine import _PrefixEntry

    eng = _stopped_engine(model, num_slots=2, max_model_len=24, num_blocks=7)
    prompt = _rand_prompt(41, 9)  # 3 blocks: 2 hashable + 1 tail
    hashes = block_hashes(prompt, 4)[:2]
    b1, b2 = eng._free.pop(), eng._free.pop()
    eng._prefix = {
        hashes[0]: _PrefixEntry(b1, refs=0, stamp=0.0),
        hashes[1]: _PrefixEntry(b2, refs=0, stamp=1.0),
    }
    eng._bid_hash = {b1: hashes[0], b2: hashes[1]}
    spare = eng._free.pop()
    eng._free.clear()  # everything else "held by running sequences"
    req = eng.submit(prompt, max_new_tokens=3)
    # need = 3 - 2 hits = 1, free = 0, and the only refs-0 entries ARE the
    # hits: pre-fix this admitted and died on `assert bid is not None`.
    eng._admit()
    assert eng._slots == [None, None]
    assert len(eng._waiting) == 1
    assert all(e.refs == 0 for e in eng._prefix.values())  # hits untouched
    # A running sequence frees a block -> the same admission now proceeds.
    eng._free.append(spare)
    eng._admit()
    assert req._sched_state == "prefill"
    assert req._sched_table == [b1, b2, spare]
    assert [e.refs for e in eng._prefix.values()] == [1, 1]


def test_engine_cancel_frees_blocks_immediately(model):
    """cancel() mid-decode returns the request's blocks to the pool within
    one scheduler iteration and terminates its consumer iterator."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4,
                    max_model_len=64, prefill_chunk=4)
    try:
        req = eng.submit([2] * 5, max_new_tokens=50)
        it = iter(req)
        next(it)  # decode underway
        eng.cancel(req)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            s = eng.stats()
            if s["running"] == 0 and s["free_blocks"] + s["cached_blocks"] == s["num_blocks"]:
                break
            time.sleep(0.01)
        else:
            pytest.fail(f"blocks not freed after cancel: {eng.stats()}")
        assert eng.stats()["cancelled"] == 1
        assert len(list(it)) < 50  # iterator terminated early
    finally:
        eng.shutdown()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_submit_after_scheduler_crash_raises(model):
    """A crashed scheduler fails new submits loudly instead of parking the
    consumer on a queue nobody will ever feed; the in-flight request is
    finished with the crash error (not hung)."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.stats import ENGINES

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                    max_model_len=32, prefill_chunk=4)

    def boom(*_a, **_k):
        raise RuntimeError("boom")

    eng._prefill_fn = boom
    req = eng.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="boom"):
        req.result(timeout=30)
    eng._thread.join(timeout=10)
    assert not eng._thread.is_alive()
    assert eng not in ENGINES  # gauges stop counting a dead engine
    with pytest.raises(RuntimeError, match="scheduler died"):
        eng.submit([4, 5, 6], max_new_tokens=2)
    with pytest.raises(RuntimeError):
        eng.check_health()


def test_pool_is_donated_and_updated_in_place(model):
    """Every prefill and decode dispatch consumes the pool it is handed (the
    arrays passed in are deleted) and hands back a pool in the same device
    buffers; ``kv_pool_not_donated`` stays 0 and the tokens are the oracle's."""
    params, cfg = model
    eng = _stopped_engine(model, num_slots=2, max_model_len=32)
    prompt = _rand_prompt(7, 6)  # two chunks of 4; the second emits token 0
    req = eng.submit(prompt, max_new_tokens=4)
    assert eng._admit() == 1
    for tick in (eng._prefill_tick, eng._prefill_tick, eng._decode_tick):
        given = dict(eng._cache)
        ptrs = {n: a.unsafe_buffer_pointer() for n, a in given.items()}
        assert tick()
        assert all(a.is_deleted() for a in given.values())
        assert {n: a.unsafe_buffer_pointer() for n, a in eng._cache.items()} == ptrs
    while not req._finished:
        assert eng._decode_tick()
    assert eng.stats()["kv_pool_not_donated"] == 0
    assert req.result(timeout=5) == _dense(params, cfg, prompt, 4)


def test_kv_pool_not_donated_counts_a_program_that_copies(model):
    """The counter reads non-zero when a program leaves its input pool alive
    (here: the same step jitted without donation), so its 0 means something."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import paged_decode_step
    from ray_tpu.serve.llm.engine import _ID_IN_FLIGHT, _ROW_POS, _ROW_TABLE, _ROW_TOKEN

    _, cfg = model
    eng = _stopped_engine(model, num_slots=1, max_model_len=32)
    def greedy_step(p, rows, c, ids):
        fed = jnp.where(rows[:, _ROW_TOKEN] == _ID_IN_FLIGHT, ids, rows[:, _ROW_TOKEN])
        logits, c = paged_decode_step(p, fed, c, rows[:, _ROW_TABLE:], rows[:, _ROW_POS], cfg)
        return logits.argmax(-1).astype("int32"), c

    eng._decode_fn = jax.jit(greedy_step)  # the engine's step without donate_argnums
    req = eng.submit([1, 2, 3], max_new_tokens=3)
    eng._admit()
    assert eng._prefill_tick()
    assert eng.stats()["kv_pool_not_donated"] == 0
    while not req._finished:
        assert eng._decode_tick()
    assert eng.stats()["kv_pool_not_donated"] == 2  # one per decode step


@functools.lru_cache(maxsize=None)
def _engine_program(cfg, kind):
    """One engine program compiled at an engine's shapes: (compiled text,
    the pool's shape, flat index of the pool's k among the arguments)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import init_paged_cache
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm.engine import _ROW_TABLE, _compiled_fns

    slots, n_max, chunk = 3, 8, 4
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, slots * n_max + 1, 4))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    decode, prefill, with_chunk = _compiled_fns(cfg)
    if kind == "decode":
        lowered = decode.lower(params, i32(slots, _ROW_TABLE + n_max), cache, i32(slots))
    elif kind == "decode_with_chunk":
        lowered = with_chunk.lower(
            params, i32(slots, _ROW_TABLE + n_max), cache, i32(slots), i32(1, chunk), i32(1, _ROW_TABLE + n_max)
        )
    else:
        lowered = prefill.lower(params, i32(1, chunk), cache, i32(1, _ROW_TABLE + n_max))
    k_arg = len(jax.tree.leaves(params)) + 1  # params, the tokens, then k and v
    return lowered.compile().as_text(), cache["k"].shape, k_arg


@pytest.mark.parametrize("kind", ["decode", "prefill", "decode_with_chunk"])
def test_engine_programs_alias_the_pool_and_never_copy_it(model, kind):
    """The compiled text of each engine program aliases both pool arguments
    to outputs, and no ``copy`` / ``dynamic-update-slice`` in it produces an
    array of the pool's shape or of one layer of it (the xs -> ys form of the
    layer scan produced both, once a layer)."""
    text, pool, k_arg = _engine_program(model[1], kind)
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert alias, "no input_output_alias in the compiled module"
    aliased = {int(m) for m in re.findall(r"\((\d+), \{\}", alias.group(1))}
    assert {k_arg, k_arg + 1} <= aliased, alias.group(0)
    for dims in (pool, pool[1:]):
        shape = re.escape("f32[" + ",".join(map(str, dims)) + "]")
        hits = re.findall(rf"= {shape}\S* (?:copy|dynamic-update-slice)\(.*", text)
        assert not hits, hits[:3]


@pytest.mark.parametrize("kind", ["decode", "prefill", "decode_with_chunk"])
def test_engine_program_names_match_the_benchmark_patterns(model, kind):
    """The benchmark finds the programs in a device trace by the
    ``trace_programs`` patterns of its serving configuration: a renamed
    callable would read as no ``decode_step_ms`` on the chip, so it fails
    here. The step that carries a chunk is found as a decode step: where every
    step carries one, nothing else would be."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "benchmarks", "configs", "mistral-7b-v0.1-serve16.json")
    with open(path) as f:
        patterns = json.load(f)["trace_programs"]
    module = re.search(r"HloModule (\S+?),", _engine_program(model[1], kind)[0]).group(1)
    found_as = "prefill" if kind == "prefill" else "decode"
    assert re.search(patterns[found_as], module), (module, patterns[found_as])
    other = patterns["prefill" if found_as == "decode" else "decode"]
    assert not re.search(other, module), (module, other)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_crash_after_donation_ends_engine_without_touching_dead_pool(model):
    """A decode program that raises AFTER its input pool was donated leaves
    the engine no pool at all: it must end as crashed with every open
    request failed (running and waiting), and never dispatch again."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                    max_model_len=32, prefill_chunk=4)
    real, calls = eng._decode_fn, []

    def donate_then_raise(p, rows, c, ids):
        calls.append(c)
        real(p, rows, c, ids)
        raise RuntimeError("boom after donation")

    eng._decode_fn = donate_then_raise
    running = eng.submit([1, 2, 3], max_new_tokens=4)
    waiting = eng.submit([4, 5, 6], max_new_tokens=4)  # one slot: stays queued
    for req in (running, waiting):
        with pytest.raises(RuntimeError, match="boom after donation"):
            req.result(timeout=30)
    eng._thread.join(timeout=10)
    assert not eng._thread.is_alive()
    assert len(calls) == 1 and all(a.is_deleted() for a in calls[0].values())
    assert all(a.is_deleted() for a in eng._cache.values())  # no pool is left
    with pytest.raises(RuntimeError, match="scheduler died"):
        eng.submit([7, 8, 9], max_new_tokens=2)
    with pytest.raises(RuntimeError):
        eng.check_health()
    assert len(calls) == 1  # nothing stepped again on the deleted pool


def test_engine_registry_tracks_live_schedulers(model):
    """stats.ENGINES holds exactly the engines whose scheduler loop is
    running — the flush-time gauge sums drop an engine at shutdown instead
    of exporting its final values forever."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.stats import ENGINES

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                    max_model_len=32, prefill_chunk=4)
    assert eng in ENGINES
    eng.shutdown()
    assert eng not in ENGINES
    # A submit racing (or following) shutdown fails loudly instead of
    # parking its consumer on a queue the drained scheduler never feeds.
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit([1, 2, 3], max_new_tokens=2)


def test_submit_rejects_request_larger_than_pool(model):
    """A request whose full extent exceeds the KV pool can never be
    admitted — submit() must say so instead of wedging the FIFO head."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                    max_model_len=40, num_blocks=4, prefill_chunk=4)
    try:
        with pytest.raises(ValueError, match="num_blocks"):
            eng.submit([1] * 10, max_new_tokens=10)  # 5 blocks > 3 usable
        # A fitting request still sails through afterwards.
        assert len(eng.submit([1] * 5, max_new_tokens=4).result(60)) == 4
    finally:
        eng.shutdown()


def test_preemption_victim_is_youngest_even_when_needy(model):
    """Youngest-victim policy holds when the block-needing sequence IS the
    youngest: it preempts itself (minimal recompute) — an older sequence
    carrying more progress is never sacrificed for it."""
    eng = _stopped_engine(model, num_slots=2, max_model_len=40)
    ra = eng.submit([3] * 6, max_new_tokens=20)
    rb = eng.submit([9] * 6, max_new_tokens=20)
    eng._admit()
    while any(r is not None and r._sched_state == "prefill" for r in eng._slots):
        eng._prefill_tick()
    assert ra._sched_state == rb._sched_state == "decode"
    # Pool dry, nothing evictable, and B — the YOUNGER sequence — is the
    # one whose next write position crosses a block boundary.
    eng._free.clear()
    eng._prefix.clear()
    eng._bid_hash.clear()
    rb._sched_pos = len(rb._sched_table) * 4
    eng._decode_tick()
    assert rb._sched_state == "waiting"  # B preempted itself...
    assert list(eng._waiting) == [rb]
    assert eng._slots[ra._sched_slot] is ra  # ...and A kept its slot
    assert ra._sched_state == "decode"
    assert eng.stats()["preemptions"] == 1


def test_buffered_timeout_frees_slot_and_blocks(model):
    """Regression: a stream=false request whose result() times out must be
    cancelled engine-side — not left generating into an unread queue while
    holding a decode slot and KV blocks."""
    from ray_tpu.serve.llm import LLMDeployment

    dep = LLMDeployment(MODEL, engine_config=dict(
        num_slots=2, block_size=4, max_model_len=64, prefill_chunk=4))
    eng = dep.engine
    try:
        with pytest.raises(TimeoutError):
            dep({"tokens": [2] * 5, "max_new_tokens": 50, "stream": False,
                 "timeout": 0.001})
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            s = eng.stats()
            if (
                s["running"] == 0
                and s["waiting"] == 0
                and s["cancelled"] == 1
                and s["free_blocks"] + s["cached_blocks"] == s["num_blocks"]
            ):
                break
            time.sleep(0.01)
        else:
            pytest.fail(f"timed-out buffered request not cancelled: {eng.stats()}")
    finally:
        eng.shutdown()


def test_sampling_seeded_reproducible(model):
    """Temperature sampling: same seed -> same tokens, different seed ->
    (overwhelmingly) different; all tokens in-vocab."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4,
                    max_model_len=32, prefill_chunk=4)
    try:
        p = _rand_prompt(3, 6)
        a = eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=16, seed=7).result(60)
        b = eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=16, seed=7).result(60)
        c = eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=16, seed=8).result(60)
        assert a == b
        assert all(0 <= t < 128 for t in a)
        assert a != c
    finally:
        eng.shutdown()


@pytest.mark.parametrize(
    "sampling",
    [dict(temperature=0.0), dict(temperature=0.9, top_k=16, seed=7)],
    ids=["greedy", "sampled"],
)
def test_resume_tokens_bit_identical(model, sampling):
    """THE migration oracle (ISSUE 14), engine half: a request resumed on a
    SECOND engine with resume_tokens= (the tokens the dead replica already
    emitted) continues BIT-IDENTICALLY — teacher-forced through chunked
    prefill like recompute preemption, nothing re-emitted — in both the
    greedy and seeded-sampling arms (the counter-based per-request RNG
    stream makes position k's draw replica-independent). KV blocks of both
    engines return to baseline."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    prompt = _rand_prompt(31, 7)
    eng_a = LLMEngine(params, cfg, num_slots=2, block_size=4,
                      max_model_len=32, prefill_chunk=4)
    eng_b = LLMEngine(params, cfg, num_slots=2, block_size=4,
                      max_model_len=32, prefill_chunk=4)
    try:
        full = eng_a.submit(prompt, max_new_tokens=8, **sampling).result(60)
        assert len(full) == 8
        for cut in (1, 4, 7, 8):
            resumed = eng_b.submit(
                prompt, max_new_tokens=8, resume_tokens=full[:cut], **sampling
            ).result(60)
            # Only the continuation is emitted; full sequence identical.
            assert resumed == full[cut:], (cut, resumed, full)
        for eng in (eng_a, eng_b):
            s = eng.stats()
            assert s["free_blocks"] + s["cached_blocks"] == s["num_blocks"], s
    finally:
        eng_a.shutdown()
        eng_b.shutdown()


def test_drain_refuses_new_submits_finishes_running(model):
    """Engine half of drain-before-retire: drain() refuses NEW submits with
    the TYPED ReplicaDrainingError (the proxy/handle reassign on it; an
    untyped error here 500s a client caught in the replica-gate/engine-
    drain race) while already-accepted requests decode to completion and
    release their blocks."""
    from ray_tpu.exceptions import ReplicaDrainingError
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4,
                    max_model_len=32, prefill_chunk=4)
    try:
        prompt = _rand_prompt(5, 6)
        req = eng.submit(prompt, max_new_tokens=6)
        eng.drain()
        with pytest.raises(ReplicaDrainingError, match="draining"):
            eng.submit(prompt, max_new_tokens=2)
        assert req.result(60) == _dense(params, cfg, prompt, 6)
        s = eng.stats()
        assert s["draining"] is True
        assert s["running"] == 0 and s["waiting"] == 0
        assert s["free_blocks"] + s["cached_blocks"] == s["num_blocks"], s
    finally:
        eng.shutdown()


def test_flight_events_recorded(model, tmp_path):
    """llm_admit/llm_prefix_hit land in the flight ring (codes 34+)."""
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    fr._reset_for_tests()
    fr.attach(str(tmp_path / "sess"), "test-llm")
    try:
        eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                        max_model_len=32, prefill_chunk=4)
        try:
            system = [1, 2, 3, 4, 5, 6, 7, 8]
            eng.submit(system + [9], max_new_tokens=2).result(60)
            eng.submit(system + [10], max_new_tokens=2).result(60)
        finally:
            eng.shutdown()
        events = [e["type"] for e in (fr.dump() or {"events": []})["events"]]
        assert "llm_admit" in events
        assert "llm_prefix_hit" in events
    finally:
        fr._reset_for_tests()


# ---------------------------------------------------------------------------
# the decode step's width (ISSUE 31): a ladder of block-table widths
# ---------------------------------------------------------------------------

# block_size 4 x max_model_len 256: n_max 64 blocks, rungs 16 / 32 / 64 blocks
# (64 / 128 / 256 tokens). A 50-token prompt with 90 new tokens crosses both
# inner boundaries mid-generation.
LADDER = dict(num_slots=3, block_size=4, max_model_len=256, prefill_chunk=8)
LONG_PROMPT, LONG_NEW = 50, 90


@pytest.fixture(scope="module")
def ladder_engine(model):
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, **LADDER)
    # Outside the decode ladder: the prefill program, built by the first chunk.
    eng.submit([1, 2, 3], max_new_tokens=2).result(60)
    yield eng
    eng.shutdown()


def _backend_compiles(cursor):
    """Names of the programs the backend built since ``COMPILES.n`` was ``cursor``."""
    from ray_tpu.serve.llm.stats import COMPILES

    return [r[3] for r in COMPILES.since(cursor) if r[2] == "backend_compile"]


def _widths_run(eng, before):
    """Rungs at which ``eng`` ran a decode step since ``before``, a copy of its ``_width_steps``."""
    return {w for w, n in eng._width_steps.items() if n > before[w]}


def test_ladder_is_a_constant_of_n_max():
    from ray_tpu.serve.llm.engine import _view_rungs

    assert _view_rungs(160) == (16, 32, 64, 128, 160)  # the benchmark's engine
    assert _view_rungs(64) == (16, 32, 64)
    assert _view_rungs(17) == (16, 17)
    for n_max in (1, 8, 16):
        assert _view_rungs(n_max) == (n_max,)  # every other CPU test's engine: today's one program


@SAMPLINGS
def test_streams_across_rungs_equal_the_full_width_streams(model, ladder_engine, monkeypatch, sampling):
    """A stream that crosses two rung boundaries mid-generation is, token for
    token, the stream of the same engine held to its one full-width rung
    (today's program) and the dense-cache oracle's, greedy (``generate()``)
    and sampled: a masked key weighs exactly 0, so a view that ends at the
    rung loses nothing, and every step but the first was dispatched while the
    step before was unfetched, at the rung ITS rows need."""
    params, cfg = model
    eng = ladder_engine
    prompt = _rand_prompt(61, LONG_PROMPT)
    before, counts = dict(eng._width_steps), dict(eng._counts)
    laddered = eng.submit(prompt, max_new_tokens=LONG_NEW, **sampling).result(120)
    assert _widths_run(eng, before) == {16, 32, 64}
    steps = eng._counts["decode_steps"] - counts["decode_steps"]
    assert steps == LONG_NEW - 1  # one a token after the first: none for a request that has ended
    assert eng._counts["decode_steps_run_ahead"] - counts["decode_steps_run_ahead"] == steps - 1
    assert eng._counts["decode_rows_dropped"] == counts["decode_rows_dropped"]
    monkeypatch.setattr(eng, "_view_rungs", (eng.n_max,))
    before = dict(eng._width_steps)
    full = eng.submit(prompt, max_new_tokens=LONG_NEW, **sampling).result(120)
    assert _widths_run(eng, before) == {eng.n_max}
    assert laddered == full == _oracle(params, cfg, prompt, LONG_NEW, **sampling)


def test_a_run_over_every_rung_compiles_nothing(ladder_engine):
    """Every rung's program was built in ``__init__``: a run that visits all
    three adds no ``backend_compile`` to the process's compile ring."""
    from ray_tpu.serve.llm.stats import COMPILES

    eng = ladder_engine
    cursor = COMPILES.n
    before = dict(eng._width_steps)
    reqs = [eng.submit(_rand_prompt(70 + i, LONG_PROMPT - 20 * i), max_new_tokens=LONG_NEW)
            for i in range(2)]
    for r in reqs:
        assert len(r.result(120)) == LONG_NEW
    assert _widths_run(eng, before) == {16, 32, 64}
    assert _backend_compiles(cursor) == []


@pytest.mark.parametrize(
    "max_model_len,d_ff,rungs",
    [(64, 72, (16,)), (256, 80, (16, 32, 64))],
    ids=["n_max_16_one_program", "n_max_64_three_programs"],
)
def test_construction_builds_one_decode_program_a_rung(max_model_len, d_ff, rungs):
    """``n_max`` <= 16 blocks: one rung and one decode program, as before the
    ladder. A wider engine builds one program a rung, all before it is ready:
    nothing was donated in vain and no step has been counted."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.stats import COMPILES, listen_for_compiles

    # A configuration no other test uses: its programs are in no jit cache yet.
    cfg = TransformerConfig(**dict(MODEL, d_ff=d_ff, dtype=jnp.dtype(MODEL["dtype"]).type))
    params = init_params(jax.random.PRNGKey(0), cfg)
    listen_for_compiles()
    since = COMPILES.n
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4, max_model_len=max_model_len,
                    prefill_chunk=4)
    try:
        # A shape that fuses: the decode step a rung, and ONE step with a chunk.
        built = [name for name in _backend_compiles(since) if "lambda" in name]
        assert eng._view_rungs == rungs and len(built) == len(rungs) + 1, built
        assert eng._fuses
        s = eng.stats()
        assert s["decode_width_steps"] == {w: 0 for w in rungs}
        assert s["kv_pool_not_donated"] == 0
        assert eng.spans.setup["decode_build_s"] > 0
        assert eng.spans.export()["iterations"] == []  # a build is no pass of the scheduler
    finally:
        eng.shutdown()


@pytest.mark.parametrize("num_blocks", [None, 26], ids=["roomy_pool", "preempting_pool"])
def test_step_width_is_the_smallest_rung_over_the_longest_active_table(model, num_blocks):
    """Driven by hand: each decode step's width is the smallest rung >= the
    longest table among ITS rows (rows in prefill, and rows whose last token
    the step before draws, not counted), and a table is no longer than the
    row's write position needs: the run-ahead step is one position further on
    than the host has seen, not one rung. The width goes up when a row crosses
    a rung and comes back down when the longest row finishes (roomy pool) or
    is preempted (a pool of 25 blocks: the long row is the youngest, loses its
    blocks at 64 tokens and comes back). ``decode_width_steps`` sums to the
    decode steps run."""
    kw = dict(num_slots=2, max_model_len=256)
    if num_blocks:
        kw["num_blocks"] = num_blocks
    eng = _stopped_engine(model, **kw)
    bs, rungs = eng.block_size, eng._view_rungs
    specs = [(_rand_prompt(81, 58), 14), (_rand_prompt(82, 9), 40)]  # 15 -> 18 blocks; 3 -> 13 blocks
    if num_blocks:
        specs.reverse()  # the long row is admitted last: the youngest is the victim
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in specs]
    launch, seen = eng._launch_step, []

    def launch_and_check(ahead_of, chunk=None):
        step = launch(ahead_of, chunk)
        if step is not None:
            riding = ahead_of.reqs if ahead_of is not None else ()
            # Where each row writes: a row the step in flight carries is one
            # position past what the host has seen of it.
            writes = [r._sched_pos + (r in riding) for r in step.reqs]
            assert [len(r._sched_table) for r in step.reqs] == [w // bs + 1 for w in writes]
            assert step.width == min(w for w in rungs if w >= max(writes) // bs + 1)
            seen.append(step.width)
        return step

    eng._launch_step = launch_and_check
    for _ in range(600):
        if all(r._finished for r in reqs):
            break
        eng._admit()
        eng._prefill_tick()
        eng._decode_tick()
    assert all(r._finished for r in reqs) and eng._inflight is None
    for r, (p, n) in zip(reqs, specs):
        assert r.result(5) == _dense(*model, p, n)
    assert set(seen) == {16, 32}  # 72 tokens at most: the 64-block rung is never needed
    assert seen[0] == 16 and (16, 32) in zip(seen, seen[1:]) and (32, 16) in zip(seen, seen[1:])
    s = eng.stats()
    assert s["decode_steps"] == sum(s["decode_width_steps"].values()) == len(seen)
    assert s["decode_width_steps"] == {w: seen.count(w) for w in rungs}
    assert s["kv_pool_not_donated"] == 0
    assert s["preemptions"] == (1 if num_blocks else 0), s
    # No row is built for a request that ends by count: a step in vain would
    # show as one more step than tokens. The preempted row's id in flight is
    # the one id dropped.
    assert s["decode_rows_dropped"] == s["preemptions"]
    if not num_blocks:
        assert seen[-1] == 16  # the long row finished first: the width came back down


# ---------------------------------------------------------------------------
# the decode loop runs one step ahead (ISSUE 34)
# ---------------------------------------------------------------------------


def _pass(eng):
    """One pass of ``_loop``, by hand."""
    eng._sweep_cancelled()
    eng._admit()
    eng._prefill_tick()
    eng._decode_tick()


def _spy_on_steps(eng):
    """Every decode step a hand-driven engine launches from here on, as (the
    step, the step in flight it was launched behind or None, the token column
    of its rows), checked as it is launched: a row feeds the id in flight if
    and only if the step in flight carries the same request in the same slot;
    every other row feeds a token the host holds, and a slot without a row is
    all zeros."""
    from ray_tpu.serve.llm.engine import _ID_IN_FLIGHT, _ROW_TOKEN

    launched, columns = [], []
    decode, launch = eng._decode_fn, eng._launch_step

    def spy_decode(p, rows, c, ids):
        columns.append(np.asarray(rows)[:, _ROW_TOKEN].tolist())
        return decode(p, rows, c, ids)

    def spy_launch(ahead_of, chunk=None):
        step = launch(ahead_of, chunk)
        if step is None:
            return None
        column = columns[-1]
        carried = dict(zip(ahead_of.slots, ahead_of.reqs)) if ahead_of is not None else {}
        rows = dict(zip(step.slots, step.reqs))
        for slot in range(eng.num_slots):
            req = rows.get(slot)
            if req is None:
                assert column[slot] == 0
            elif carried.get(slot) is req:
                assert column[slot] == _ID_IN_FLIGHT
            else:
                assert column[slot] == req._sched_generated[-1]
        launched.append((step, ahead_of, column))
        return step

    eng._decode_fn, eng._launch_step = spy_decode, spy_launch
    return launched


def _drive(eng, reqs, passes=400):
    for _ in range(passes):
        if all(r._finished for r in reqs):
            break
        _pass(eng)
    assert all(r._finished for r in reqs) and eng._inflight is None
    s = eng.stats()
    assert s["free_blocks"] + s["cached_blocks"] == s["num_blocks"], s
    assert s["kv_pool_not_donated"] == 0
    return s


@SAMPLINGS
def test_a_request_that_ends_by_count_has_no_row_in_the_next_step(model, sampling):
    """A short request and a long one decode side by side with a third
    waiting. The short one ends by count with step N's token: step N+1, built
    before N was fetched, already has no row for it (no step and no row is
    spent on a finished request, nothing is dropped), and the request that
    takes its slot while the long one's step is in flight feeds its own first
    token from the host, never an id the slot's row drew before. All three
    streams are the oracle's."""
    from ray_tpu.serve.llm.engine import _ID_IN_FLIGHT

    params, cfg = model
    eng = _stopped_engine(model, num_slots=2, max_model_len=48)
    launched = _spy_on_steps(eng)
    specs = [(_rand_prompt(101, 4), 4), (_rand_prompt(102, 4), 30), (_rand_prompt(103, 3), 6)]
    reqs = [eng.submit(p, max_new_tokens=n, **sampling) for p, n in specs]
    short, long_, successor = reqs
    s = _drive(eng, reqs)
    for r, (p, n) in zip(reqs, specs):
        assert r.result(5) == _oracle(params, cfg, p, n, **sampling)
    # Every token but a request's first came from one row of one step.
    assert sum(len(step.reqs) for step, _, _ in launched) == sum(n - 1 for _, n in specs)
    assert s["decode_steps"] == len(launched) and s["decode_rows_dropped"] == 0
    # The batch never empties between the short request's first step and the
    # long one's last: every step but the one that primed the pipeline rode.
    assert s["decode_steps_run_ahead"] == len(launched) - 1
    assert [ahead_of is None for _, ahead_of, _ in launched] == [True] + [False] * (len(launched) - 1)
    # The short request's last row is in the step that drew its 4th token ...
    last = max(i for i, (step, _, _) in enumerate(launched) if short in step.reqs)
    assert sum(short in step.reqs for step, _, _ in launched) == 4 - 1
    # ... and the successor took that slot while the long row's steps went on.
    first = min(i for i, (step, _, _) in enumerate(launched) if successor in step.reqs)
    step, ahead_of, column = launched[first]
    slot = step.slots[step.reqs.index(successor)]
    assert first > last and slot == launched[last][0].slots[launched[last][0].reqs.index(short)]
    assert ahead_of is not None and long_ in ahead_of.reqs and successor not in ahead_of.reqs
    assert column[slot] != _ID_IN_FLIGHT and column[1 - slot] == _ID_IN_FLIGHT


@SAMPLINGS
def test_a_cancel_that_lands_while_a_step_is_in_flight_drops_its_id(model, sampling):
    """Two requests decode, a third waits. One is cancelled while a step that
    carries its row is in flight: the sweep frees its blocks at once (the step
    in flight may still write its row into them), the waiting request takes
    the slot AND those blocks and prefills into them behind that step in
    device order, the id fetched for the cancelled row is dropped and counted,
    never emitted, and the successor's and the survivor's streams are the
    oracle's."""
    params, cfg = model
    eng = _stopped_engine(model, num_slots=2, max_model_len=48, num_blocks=17)
    launched = _spy_on_steps(eng)
    specs = [(_rand_prompt(111, 6), 20), (_rand_prompt(112, 5), 20), (_rand_prompt(113, 7), 12)]
    reqs = [eng.submit(p, max_new_tokens=n, **sampling) for p, n in specs]
    doomed, survivor, successor = reqs
    while len(doomed._sched_generated) < 5:
        _pass(eng)
    step = eng._inflight
    assert step is not None and doomed in step.reqs and survivor in step.reqs
    emitted, blocks = list(doomed._sched_generated), set(doomed._sched_table)
    free = len(eng._free) + len(eng._lru)  # its one full prompt block is the prefix cache's: evictable, not free
    eng.cancel(doomed)
    eng._sweep_cancelled()
    # Freed at once, the step that carries its row still in flight.
    assert doomed._finished and len(eng._free) + len(eng._lru) == free + len(blocks)
    assert eng._inflight is step
    _pass(eng)  # admits the successor into the freed slot and blocks, then lands the step
    assert successor._sched_slot == step.slots[step.reqs.index(doomed)]
    assert set(successor._sched_table) <= blocks
    assert eng.stats()["decode_rows_dropped"] == 1
    s = _drive(eng, [survivor, successor])
    assert s["decode_rows_dropped"] == 1 and s["cancelled"] == 1
    assert doomed._sched_generated == emitted == list(doomed)  # the queue ends after what was emitted before the cancel
    for r, (p, n) in list(zip(reqs, specs))[1:]:
        assert r.result(5) == _oracle(params, cfg, p, n, **sampling)
    at = [st for st, _, _ in launched].index(step)
    assert all(doomed not in st.reqs for st, _, _ in launched[at + 1:])


@SAMPLINGS
def test_a_preemption_that_lands_while_a_step_is_in_flight_drops_its_id(model, sampling):
    """A pool too small for two long rows: the younger is preempted while
    building step N+1, with its row of step N in flight. That id is dropped
    at its fetch and counted; readmitted, the request draws the same token
    again at the same index, so both streams are still the oracle's."""
    params, cfg = model
    eng = _stopped_engine(model, num_slots=2, max_model_len=40, num_blocks=13)
    _spy_on_steps(eng)
    specs = [(_rand_prompt(121, 6), 20), (_rand_prompt(122, 6), 20)]
    reqs = [eng.submit(p, max_new_tokens=n, **sampling) for p, n in specs]
    s = _drive(eng, reqs)
    assert s["preemptions"] >= 1 and s["decode_rows_dropped"] == s["preemptions"]
    assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0  # the youngest is the victim
    for r, (p, n) in zip(reqs, specs):
        assert r.result(5) == _oracle(params, cfg, p, n, **sampling)


@pytest.mark.parametrize("how", ["drain", "shutdown"])
def test_leaving_with_a_step_in_flight(model, how):
    """``drain()`` with a step in flight: the accepted request decodes to its
    end, run-ahead all the way, and its stream is the oracle's.
    ``shutdown()`` with a step in flight (the scheduler is held inside its
    6th dispatch until the stop is set): the 5th step is still fetched and
    emitted, the 6th is dropped unfetched, the request ends with the typed
    shutdown error after exactly six of the oracle's tokens, nothing stays in
    flight and every block is back."""
    from ray_tpu.exceptions import ReplicaDrainingError
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=4, max_model_len=64, prefill_chunk=4)
    prompt, n = _rand_prompt(131, 6), 50
    want = _oracle(params, cfg, prompt, n, temperature=0.8, seed=5)
    decode, dispatched = eng._decode_fn, []
    sixth, stop_is_set = threading.Event(), threading.Event()

    def held_at_the_sixth(*args):
        dispatched.append(1)
        if how == "shutdown" and len(dispatched) == 6:
            sixth.set()
            assert stop_is_set.wait(30)
        return decode(*args)

    eng._decode_fn = held_at_the_sixth
    try:
        req = eng.submit(prompt, max_new_tokens=n, temperature=0.8, seed=5)
        if how == "drain":
            it = iter(req)
            got = [next(it), next(it)]  # decode underway: from here on a step is in flight
            eng.drain()
            assert got + list(it) == want
            s = eng.stats()
            assert s["decode_steps"] == n - 1 and s["decode_steps_run_ahead"] == n - 2
        else:
            assert sixth.wait(60)
            eng._stop.set()
            stop_is_set.set()
            eng.shutdown()
            assert not eng._thread.is_alive() and eng._inflight is None
            with pytest.raises(ReplicaDrainingError):
                req.result(5)
            assert req._sched_generated == want[:6]  # the first by the prefill, one a fetched step
            s = eng.stats()
            assert s["decode_steps"] == 6
            with pytest.raises(RuntimeError, match="shut down"):
                eng.submit(prompt, max_new_tokens=2)
        assert s["decode_rows_dropped"] == 0  # a step never fetched drops nothing: its requests ended with the loop
        assert s["running"] == 0 and s["free_blocks"] + s["cached_blocks"] == s["num_blocks"], s
    finally:
        stop_is_set.set()
        eng.shutdown()


# ---------------------------------------------------------------------------
# replica stream hygiene (no cluster: Replica driven directly)
# ---------------------------------------------------------------------------


def _llm_replica(engine_config=None):
    import cloudpickle

    from ray_tpu.serve._private.replica import Replica
    from ray_tpu.serve.llm import LLMDeployment

    spec = cloudpickle.dumps(
        (
            LLMDeployment,
            (MODEL,),
            {
                "engine_config": dict(
                    num_slots=2, block_size=4, max_model_len=64,
                    prefill_chunk=4, **(engine_config or {})
                )
            },
        )
    )
    return Replica(spec)


def _start_stream(replica, body):
    env = replica.handle_http_request(
        "POST", "/llm", {}, json.dumps(body).encode(), {}
    )
    assert "__serve_stream__" in env, env
    assert env["content_type"] == "text/event-stream"
    return env["__serve_stream__"]


def test_cancel_stream_frees_decode_slot_and_blocks(model):
    """Satellite: a client disconnect (cancel_stream) mid-decode frees the
    request's decode slot and KV blocks IMMEDIATELY via on_disconnect — not
    via the 5-minute idle reaper, and not only at the pump's next yield."""
    replica = _llm_replica()
    eng = replica._callable.engine
    try:
        sid = _start_stream(
            replica, {"tokens": [2] * 5, "max_new_tokens": 400 // 8}
        )
        # First chunk proves decode is underway.
        out = replica.next_stream_chunk(sid)
        assert out["chunks"] and not out["done"]
        assert eng.stats()["running"] == 1
        assert replica.cancel_stream(sid) is True
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            s = eng.stats()
            if (
                s["running"] == 0
                and s["cancelled"] == 1
                and s["free_blocks"] + s["cached_blocks"] == s["num_blocks"]
            ):
                break
            time.sleep(0.01)
        else:
            pytest.fail(f"slot/blocks not freed after cancel_stream: {eng.stats()}")
        assert replica.next_stream_chunk(sid) is None  # stream is gone
    finally:
        replica.prepare_for_shutdown()


def test_idle_reap_cancels_stale_streams(model):
    """First direct test of _reap_idle_streams_locked: a stream nobody
    pumped for >5 minutes is torn down on the next stream registration —
    pump cancelled, on_disconnect fired (engine blocks freed)."""
    replica = _llm_replica()
    eng = replica._callable.engine
    try:
        sid = _start_stream(replica, {"tokens": [3] * 5, "max_new_tokens": 50})
        assert replica.next_stream_chunk(sid)["chunks"]
        pump = replica._streams[sid]
        pump.last_pump -= 301.0  # idle past the reap threshold
        sid2 = _start_stream(replica, {"tokens": [4] * 5, "max_new_tokens": 3})
        assert sid not in replica._streams
        assert pump.cancelled.is_set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if eng.stats()["cancelled"] >= 1:
                break
            time.sleep(0.01)
        else:
            pytest.fail(f"reap did not cancel the engine request: {eng.stats()}")
        # The fresh stream still works end to end.
        chunks, done = [], False
        deadline = time.monotonic() + 30
        while not done and time.monotonic() < deadline:
            out = replica.next_stream_chunk(sid2)
            chunks += out["chunks"]
            done = out["done"]
        assert done and any(b"[DONE]" in c for c in chunks)
    finally:
        replica.prepare_for_shutdown()


# ---------------------------------------------------------------------------
# router (no cluster: bare Router with a hand-fed table)
# ---------------------------------------------------------------------------


def _bare_router(n_replicas=1, max_q=1):
    from ray_tpu.serve._private.router import Router

    r = Router(None)
    r._table = {
        "dep": {
            "route_prefix": "/dep",
            "replicas": [
                {"actor_name": f"rep{i}", "max_concurrent_queries": max_q}
                for i in range(n_replicas)
            ],
        }
    }
    return r


def test_release_unblocks_waiting_assign_within_10ms():
    """Satellite: a saturated assign parks on the Condition and a release()
    hands it the slot in <10 ms (the old path busy-slept 10 ms per probe)."""
    router = _bare_router(n_replicas=1, max_q=1)
    waits = []
    for _ in range(3):  # min-of-3: immune to a stray scheduler hiccup
        held = router.assign_replica("dep", timeout_s=5)
        woke = {}

        def blocked_assign():
            r = router.assign_replica("dep", timeout_s=5)
            woke["t"] = time.perf_counter()
            woke["r"] = r

        t = threading.Thread(target=blocked_assign)
        t.start()
        time.sleep(0.2)  # let it park on the condition
        assert "t" not in woke
        t0 = time.perf_counter()
        router.release(held, deployment="dep")
        t.join(timeout=5)
        assert "t" in woke, "assign never woke after release"
        waits.append(woke["t"] - t0)
        router.release(woke["r"], deployment="dep")
    assert min(waits) < 0.010, f"release->assign handoff too slow: {waits}"


def test_assign_deadline_semantics_preserved():
    router = _bare_router(n_replicas=1, max_q=1)
    router.assign_replica("dep", timeout_s=5)
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        router.assign_replica("dep", timeout_s=0.3)
    dt = time.perf_counter() - t0
    assert 0.25 <= dt < 3.0


def test_prefix_hint_affinity_and_least_depth_fallback():
    """Same hint -> same replica (stable); saturated hint target spills to
    the least-loaded unsaturated replica."""
    router = _bare_router(n_replicas=3, max_q=2)
    hint = "a" * 40
    r1 = router.assign_replica("dep", prefix_hint=hint)
    r2 = router.assign_replica("dep", prefix_hint=hint)
    assert r1["actor_name"] == r2["actor_name"]  # both slots on the target
    # Target now saturated: the spill goes to the LEAST-loaded survivor.
    others = [f"rep{i}" for i in range(3) if f"rep{i}" != r1["actor_name"]]
    router._inflight[others[0]] = 1  # load one survivor
    r3 = router.assign_replica("dep", prefix_hint=hint)
    assert r3["actor_name"] == others[1]
    # model_id affinity unchanged: stable replica (fresh router — the one
    # above is deliberately saturated).
    router2 = _bare_router(n_replicas=3, max_q=2)
    m1 = router2.assign_replica("dep", model_id="m")
    m2 = router2.assign_replica("dep", model_id="m")
    assert m1["actor_name"] == m2["actor_name"]


# ---------------------------------------------------------------------------
# end to end over HTTP (ONE module-scoped cluster)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llm_serve(model):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    serve.start()
    app = serve.deployment(LLMDeployment).bind(
        MODEL,
        engine_config=dict(
            num_slots=4, block_size=4, max_model_len=64, prefill_chunk=8
        ),
    )
    handle = serve.run(app, route_prefix="/llm")
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


def _sse_tokens(resp):
    toks, buf = [], b""
    while True:
        chunk = resp.read(256)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            if not event.startswith(b"data: "):
                continue
            payload = event[6:]
            if payload == b"[DONE]":
                return toks, True
            toks.append(json.loads(payload)["token"])
    return toks, False


def test_http_sse_stream_matches_oracle(model, llm_serve):
    """deploy -> curl-style SSE: streamed greedy tokens equal the dense
    generate() oracle (replica params are seed-deterministic)."""
    from ray_tpu import serve

    params, cfg = model
    prompt = _rand_prompt(21, 7)
    host, port = serve.http_address()
    req = urllib.request.Request(
        f"http://{host}:{port}/llm",
        data=json.dumps({"tokens": prompt, "max_new_tokens": 6}).encode(),
    )
    resp = urllib.request.urlopen(req, timeout=120)
    assert resp.headers.get("Content-Type", "").startswith("text/event-stream")
    toks, done = _sse_tokens(resp)
    assert done
    assert toks == _dense(params, cfg, prompt, 6)


def test_handle_prefix_hint_routes_to_warm_replica(model, llm_serve):
    """Cache-aware routing end to end: two buffered requests sharing a
    system prompt and carrying its prefix_route_hint land on the same
    replica — the second one hits the prefix cache."""
    import ray_tpu
    from ray_tpu.serve.llm import prefix_route_hint

    system = [5, 9, 3, 7, 1, 2, 8, 4]
    hint = prefix_route_hint(system, 4)
    h = llm_serve.options(prefix_hint=hint)
    out1 = ray_tpu.get(
        h.remote({"tokens": system + [11], "max_new_tokens": 3, "stream": False}),
        timeout=120,
    )
    out2 = ray_tpu.get(
        h.remote({"tokens": system + [13], "max_new_tokens": 3, "stream": False}),
        timeout=120,
    )
    assert len(out1["tokens"]) == 3 and len(out2["tokens"]) == 3
    stats = ray_tpu.get(h.get_stats.remote(), timeout=60)
    assert stats["prefix_hit_blocks"] >= 2, stats


@pytest.mark.slow
def test_concurrent_streams_sweep(model, llm_serve):
    """Full concurrency sweep (slow): 8 closed-loop SSE streams against one
    replica — every stream completes, every completion matches the oracle,
    and mid-decode admissions actually happened (admitted > slots)."""
    from ray_tpu import serve

    params, cfg = model
    host, port = serve.http_address()
    errs, done_counts = [], []

    def stream(i):
        try:
            rng = np.random.default_rng(100 + i)
            for j in range(3):
                prompt = rng.integers(0, 128, 6).tolist()
                n = int(rng.integers(2, 8))
                req = urllib.request.Request(
                    f"http://{host}:{port}/llm",
                    data=json.dumps({"tokens": prompt, "max_new_tokens": n}).encode(),
                )
                toks, done = _sse_tokens(urllib.request.urlopen(req, timeout=300))
                assert done and toks == _dense(params, cfg, prompt, n)
                done_counts.append(1)
        except Exception as e:  # noqa: BLE001
            errs.append(f"stream {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=stream, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errs, errs
    assert sum(done_counts) == 24
