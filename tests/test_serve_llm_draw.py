"""The draw inside the serving programs (ISSUE 29): ``draw_tokens``'
distribution, top-k set and key, and an engine that fetches token ids and
never logits. CPU backend, the tiny model of ``test_serve_llm_engine.py``,
one engine for the module (its programs come from the process-level jit cache).
"""

import time

import numpy as np
import pytest

from ray_tpu.serve.llm import stats

MODEL = dict(
    vocab_size=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=64, dtype="float32", remat=False,
)
ENGINE = dict(num_slots=3, block_size=4, max_model_len=32, prefill_chunk=4)
IT = {name: i for i, name in enumerate(stats.ITERATION_FIELDS)}
SEEDS = 4000
# chi-square, 15 degrees of freedom: the 1e-6 quantile is 54.9. The draws are a
# fixed function of fixed seeds, so a pass is a pass every time.
CHI2_15 = 55.0


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**dict(MODEL, dtype=jnp.dtype(MODEL["dtype"]).type))
    return init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture(scope="module")
def engine(model):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(*model, **ENGINE)
    yield eng
    eng.shutdown()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, MODEL["vocab_size"], n).tolist()


def _draw(logits, temperature, top_k, seeds, counter=0):
    """``draw_tokens`` of one logits row under each of ``seeds`` (64-bit ints)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import draw_tokens

    n = len(seeds)
    halves = np.array([[s & 0xFFFFFFFF, s >> 32] for s in seeds], np.uint32)
    return np.asarray(
        jax.jit(draw_tokens)(
            jnp.tile(jnp.asarray(logits, jnp.float32), (n, 1)),
            jnp.full((n,), temperature, jnp.float32),
            jnp.full((n,), top_k, jnp.int32),
            jnp.asarray(halves),
            jnp.full((n,), counter, jnp.int32),
        )
    )


def _softmax(x):
    p = np.exp(x - x.max())
    return p / p.sum()


LOGITS = (np.random.default_rng(29).standard_normal(16) * 2.0).astype(np.float32)


# ---------------------------------------------------------------------------
# the draw itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.7, 1.0])
def test_draws_follow_softmax_of_logits_over_temperature(temperature):
    toks = _draw(LOGITS, temperature, 0, range(SEEDS))
    want = SEEDS * _softmax(LOGITS.astype(np.float64) / temperature)
    got = np.bincount(toks, minlength=len(LOGITS))
    assert ((got - want) ** 2 / want).sum() < CHI2_15, (got, want)


def test_top_k_draws_stay_in_the_k_largest_and_follow_their_softmax():
    toks = _draw(LOGITS, 1.0, 5, range(SEEDS))
    top5 = np.argsort(LOGITS)[-5:]
    assert set(toks) == set(top5)  # none outside, and each of the five is reachable
    want = SEEDS * _softmax(LOGITS[top5].astype(np.float64))
    got = np.bincount(toks, minlength=len(LOGITS))[top5]
    assert ((got - want) ** 2 / want).sum() < 33.4  # 4 degrees of freedom, 1e-6


def test_ties_at_the_top_k_threshold_stay_drawable():
    logits = np.array([3.0, 1.0, 2.0, 1.0, -1.0, 1.0, 0.5, 2.5], np.float32)
    # top_k = 4: 3.0, 2.5, 2.0 and the threshold 1.0, which three entries share
    assert set(_draw(logits, 1.0, 4, range(SEEDS))) == {0, 7, 2, 1, 3, 5}
    # a top_k of the vocabulary or more cuts nothing
    assert set(_draw(logits, 2.0, 8, range(SEEDS))) == set(range(8))
    assert set(_draw(logits, 2.0, 10**6, range(SEEDS))) == set(range(8))


@pytest.mark.parametrize("k", [1, 2, 7, 100, 999, 1000])
def test_kth_largest_is_the_sort_s(k):
    import jax

    from ray_tpu.models.generate import _kth_largest

    x = np.random.default_rng(k).standard_normal((6, 1000)).astype(np.float32)
    x[0, :10] = x[0, 10:20]  # ties
    x[1, :4] = [0.0, -0.0, np.float32(1e-40), -np.inf]  # both zeros, a denormal, -inf
    x[2] = np.abs(x[2])  # one sign only
    x[3] = -np.abs(x[3])
    got = np.asarray(jax.jit(_kth_largest)(x, np.full((6,), k, np.int32)))
    assert (got == np.sort(x, axis=-1)[:, -k]).all()


def test_greedy_is_the_first_argmax_of_the_raw_logits():
    logits = np.array([0.5, 2.0, -1.0, 2.0, 2.0], np.float32)
    assert _draw(logits, 0.0, 0, [1, 2, 3]).tolist() == [1, 1, 1]
    assert _draw(logits, -1.0, 3, [1, 2, 3]).tolist() == [1, 1, 1]


def test_all_64_bits_of_the_seed_and_the_counter_take_part():
    flat = np.zeros(64, np.float32)  # uniform: a stream of 32 draws is 192 bits
    stream = lambda seed: [int(_draw(flat, 1.0, 0, [seed], counter=i)[0]) for i in range(32)]  # noqa: E731
    low, high, both = stream(5), stream(5 + (1 << 32)), stream(5 + (1 << 63))
    assert low != high and low != both and high != both
    assert stream(5) == low
    assert len(set(low)) > 8  # the counter moves the draw


def test_a_row_draws_the_same_token_in_any_row_beside_any_others():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import draw_tokens

    rng = np.random.default_rng(3)
    logits = rng.standard_normal((5, 64)).astype(np.float32)
    temperature = np.array([0.8, 0.0, 1.0, 0.6, 1.3], np.float32)
    top_k = np.array([0, 0, 9, 3, 0], np.int32)
    seed = rng.integers(0, 2**32, (5, 2), dtype=np.uint32)
    counter = np.array([0, 4, 17, 2, 9], np.int32)
    draw = jax.jit(draw_tokens)
    batch = np.asarray(draw(*map(jnp.asarray, (logits, temperature, top_k, seed, counter))))
    for order in ([4, 3, 2, 1, 0], [2, 0, 4, 1, 3]):
        args = [jnp.asarray(a[order]) for a in (logits, temperature, top_k, seed, counter)]
        assert np.asarray(draw(*args)).tolist() == batch[order].tolist()
    for i in range(5):
        args = [jnp.asarray(a[i:i + 1]) for a in (logits, temperature, top_k, seed, counter)]
        assert int(draw(*args)[0]) == batch[i]


# ---------------------------------------------------------------------------
# the engine that carries it
# ---------------------------------------------------------------------------

MIXED = [dict(temperature=0.0), dict(temperature=1.0, seed=11), dict(temperature=0.8, top_k=6, seed=12)]


def _mixed_step(engine, base):
    """A greedy row, a sampled row and a top-k row decoding side by side:
    their tokens, and the most rows one step of theirs carried."""
    t0 = time.monotonic_ns()
    prompts = [_prompt(base + i, 5 + i) for i in range(3)]
    reqs = [engine.submit(p, max_new_tokens=10, **kw) for p, kw in zip(prompts, MIXED)]
    outs = [r.result(timeout=60) for r in reqs]
    time.sleep(0.1)  # the scheduler closes its last pass after the consumer has its tokens
    flat, width = engine.spans.export()["iterations"], len(stats.ITERATION_FIELDS)
    rows = [flat[i + IT["rows"]] for i in range(0, len(flat), width) if flat[i + IT["t_start_ns"]] >= t0]
    return prompts, outs, max(rows)


def test_greedy_rows_are_the_dense_oracle_s_beside_sampled_rows(model, engine):
    import jax.numpy as jnp

    from ray_tpu.models.generate import generate

    params, cfg = model
    prompts, outs, rows = _mixed_step(engine, 70)
    assert rows == 3  # they did share steps
    dense = generate(params, jnp.asarray([prompts[0]], jnp.int32), cfg, max_new_tokens=10, temperature=0.0)
    assert np.asarray(dense)[0].tolist() == outs[0]
    # and the sampled rows drew what each draws alone, whatever the slot
    for p, kw, together in list(zip(prompts, MIXED, outs))[1:]:
        assert engine.submit(p, max_new_tokens=10, **kw).result(timeout=60) == together


def test_a_mixed_step_compiles_nothing_after_a_greedy_warm_up(engine):
    """What the benchmark's set-up does: greedy requests build both programs,
    every branch of the draw included (temperature and top_k are traced)."""
    assert len(engine.submit(_prompt(80, 9), max_new_tokens=4).result(timeout=60)) == 4
    t0 = time.monotonic_ns()
    _, outs, rows = _mixed_step(engine, 81)
    assert rows == 3 and all(len(o) == 10 for o in outs)
    built = [r for r in stats.compile_records() if r[0] >= t0 and r[2] == "backend_compile"]
    assert built == []


def test_no_logits_reach_the_host(engine):
    """Prefill, decode and a resumed request (its tail teacher-forced through
    the prefill program, which draws the next token): ids only."""
    prompt = _prompt(90, 7)
    kw = dict(temperature=0.9, top_k=16, seed=2**40 + 7)
    full = engine.submit(prompt, max_new_tokens=8, **kw).result(timeout=60)
    resumed = engine.submit(prompt, max_new_tokens=8, resume_tokens=full[:3], **kw)
    assert resumed.result(timeout=60) == full[3:]
    s = engine.stats()
    assert s["host_logit_rows"] == 0 and s["kv_pool_not_donated"] == 0
    assert s["span_ns"]["llm.decode.fetch"] > 0 and s["span_ns"]["llm.prefill.fetch"] > 0
    assert s["iterations"]["decode"] > 0 and s["iterations"]["prefill"] + s["iterations"]["mixed"] > 0


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_host_logit_rows_counts_a_program_that_returns_logits(model):
    """The counter reads non-zero, and the engine ends, when a program hands
    back logits (here: the step as it was before the draw moved into it)."""
    import jax

    from ray_tpu.models.generate import paged_decode_step
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.engine import _ROW_POS, _ROW_TABLE, _ROW_TOKEN

    params, cfg = model
    eng = LLMEngine(params, cfg, **ENGINE)
    eng._decode_fn = jax.jit(
        lambda p, rows, c, ids: paged_decode_step(
            p, rows[:, _ROW_TOKEN], c, rows[:, _ROW_TABLE:], rows[:, _ROW_POS], cfg
        ),
        donate_argnums=2,
    )
    req = eng.submit(_prompt(95, 5), max_new_tokens=4)
    with pytest.raises(RuntimeError, match="not one token id a row"):
        req.result(timeout=60)
    eng._thread.join(timeout=10)
    assert eng.stats()["host_logit_rows"] == ENGINE["num_slots"]
