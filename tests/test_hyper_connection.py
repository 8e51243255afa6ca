"""Manifold-constrained hyper-connections and YaRN-scaled latent attention (PR
47): ``ops/hyper_connection.py`` against the benchmark's plain reference
(``benchmarks/architectures/Xing4_0ForCausalLM/reference.py``, which imports
nothing of it), the layer stack over a paged cache against the reference's
whole forward pass at positions past YaRN's original range, the Pallas walk
under YaRN's softmax scale, the engine's records, and what refuses the new
fields by name. Float32 throughout: a CPU run says what is computed, not how fast."""

import importlib

import numpy as np
import pytest

PUBLISHED = dict(
    hidden_size=128, intermediate_size=256, num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=5,
    first_k_dense_replace=2, vocab_size=512, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=16,
    v_head_dim=32, moe_intermediate_size=64, n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=2, rms_norm_eps=1e-6, rope_theta=10000, tie_word_embeddings=False, torch_dtype="float32",
    # 32 original positions: the sequences below run to 100. Of the 8 rotary pairs the ramp covers 0..2.
    rope_scaling=dict(type="yarn", factor=8, original_max_position_embeddings=32, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    hc_mult=4, hc_sinkhorn_iters=4, hc_eps=1e-6,  # 4 of the published 20 iterations: unrolled, the CPU's compiler takes 40 s a program at 20
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
    n_group=1, topk_group=1, norm_topk_prob=True, topk_method="noaux_tc", scoring_func="sigmoid", moe_layer_freq=1,
    ep_size=1, attention_bias=False, hidden_act="silu",
)
ARCH = {"name": "these tests", "architecture": "Xing4_0ForCausalLM"}


def _part(part):
    from benchmarks.harness import registry

    return registry.load_architecture({**ARCH, "bench_dir": registry.BENCH_DIR}, part)


def _config(**over):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    model = _part("config").model_config({**PUBLISHED, **over}, 256, "float32")
    model.update(dtype=jnp.float32, param_dtype=jnp.float32)
    return TransformerConfig(**model)


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models.transformer import init_params

    cfg = _config()
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, PUBLISHED["vocab_size"], n)


# -- the operation ---------------------------------------------------------------


def _sub_layer(n, alpha_res, seed=0, T=6, D=32):
    """A stream, a sub-layer's leaves and a branch, as the program and as the reference take them."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    width = 2 * n + n * n
    stream = jnp.asarray(rng.normal(size=(2, T // 2, n * D)), jnp.float32)
    phi = jnp.asarray(rng.normal(size=(width, n * D)) * (n * D) ** -0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(width,)) * 0.5, jnp.float32)
    alpha = jnp.asarray([1.0, 1.0, alpha_res], jnp.float32)
    w_branch = jnp.asarray(rng.normal(size=(D, D)) * D**-0.5, jnp.float32)
    m = dict(rms_norm_eps=1e-6, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
    return stream, phi, b, alpha, (lambda u: jnp.tanh(u @ w_branch)), m


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("alpha_res", [1.0, 60.0], ids=["spread", "at_the_clamp"])
def test_mix_and_join_are_the_references_sub_layer(n, alpha_res):
    """``mix`` -> branch -> ``join`` against ``reference.hyper_connection`` over
    the same leaves; with ``alpha_res`` 60 the logits of ``H_res`` run to +-100
    and beyond and are clipped to +-30 (exp(30) / exp(-30): Sinkhorn still ends
    on a doubly stochastic matrix, a permutation nearly)."""
    import jax.numpy as jnp

    from ray_tpu.ops import hyper_connection

    stream, phi, b, alpha, branch, m = _sub_layer(n, alpha_res)
    cfg = _config(hc_mult=n, hc_sinkhorn_iters=20)
    u, post, res = hyper_connection.mix(stream, phi, b, alpha, cfg)
    got = hyper_connection.join(stream, branch(u), post, res)
    T, D = stream.shape[0] * stream.shape[1], stream.shape[-1] // n
    X = stream.reshape(T, n, D)
    w = {"phi_t": phi, "b": b, "alpha": alpha}
    reference = _part("reference")
    want = reference.hyper_connection(w, X, m, branch)
    assert jnp.allclose(got.reshape(T, n, D), want, atol=2e-5), float(jnp.abs(got.reshape(T, n, D) - want).max())
    pre, _, _ = reference.hyper_coefficients(w, X, m)
    assert jnp.allclose(u.reshape(T, D), jnp.einsum("ti,tid->td", pre, X), atol=2e-5)
    if alpha_res > 1:  # the clamp was reached, on both sides
        flat = X.reshape(T, n * D)
        logits = alpha_res * (flat @ phi.T)[:, 2 * n :] * (jnp.mean(flat * flat, -1, keepdims=True) + 1e-6) ** -0.5 + b[2 * n :]
        assert float(logits.max()) > 30 and float(logits.min()) < -30


def test_h_res_is_doubly_stochastic_after_twenty_iterations(model):
    """Columns were normalised last: they sum to 1 (to ``hc_eps``). Rows nearly:
    at the draw the benchmark serves (``b`` + 2 on the diagonal, logits of
    standard deviation ~1) twenty iterations leave most tokens' rows within
    1e-4 of 1 and the slowest within 3e-2 (ISSUE 47 expected 1e-4 of all)."""
    import jax.numpy as jnp

    from ray_tpu.ops import hyper_connection

    params, cfg = model[0], _config(hc_sinkhorn_iters=20)
    stream = hyper_connection.widen(params["embed"][_tokens(3, 64)][None], cfg.hc_mult) * (1 + jnp.arange(4 * 128) / 512.0)
    for stack, layer, sub in (("dense_layers", 0, "attn"), ("layers", 2, "mlp")):
        lp = {name: leaf[layer] for name, leaf in params[stack].items() if name.startswith(f"hc_{sub}")}
        _, post, res = hyper_connection.coefficients(stream, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_b"], lp[f"hc_{sub}_alpha"], cfg)
        H = jnp.stack([jnp.stack(row, -1) for row in res], -2)  # [T, n, n]
        assert H.shape == (64, 4, 4) and H.dtype == jnp.float32 and all(p.dtype == jnp.float32 for p in post)
        off = jnp.abs(H.sum(-1) - 1.0).max(-1)  # a token's worst row
        assert jnp.allclose(H.sum(-2), 1.0, atol=1e-5) and float(jnp.median(off)) < 1e-4 and float(off.max()) < 3e-2
        assert 0.3 < float(jnp.diagonal(H, axis1=-2, axis2=-1).mean()) < 0.95  # neither the identity nor uniform
        assert 0 < float(jnp.stack(post).min()) and float(jnp.stack(post).max()) < 2 and float(jnp.stack(post).std()) > 0.1


def test_the_coefficients_stay_float32_under_a_bfloat16_stream():
    """A served stream is bfloat16; its 2n + n^2 coefficients a token are float32
    from ``x~`` to ``H_res``: no bfloat16 value between the stream's cast and the
    coefficients, and the product with ``phi`` at the highest precision (on a TPU
    the default rounds a float32 operand to bfloat16, which no CPU run shows)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import hyper_connection

    stream, phi, b, alpha, _, _ = _sub_layer(4, 1.0)
    cfg = _config()
    jaxpr = jax.make_jaxpr(lambda s: hyper_connection.coefficients(s, phi, b, alpha, cfg))(stream.astype(jnp.bfloat16))
    eqns = jaxpr.jaxpr.eqns
    assert eqns[0].primitive.name in ("reshape", "convert_element_type")
    floats = [v.aval.dtype for e in eqns for v in e.outvars if jnp.issubdtype(v.aval.dtype, jnp.floating)]
    assert floats.count(jnp.dtype(jnp.bfloat16)) <= 1 and all(d == jnp.float32 for d in floats if d != jnp.bfloat16)
    (dot,) = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dot.params["precision"] is not None and "HIGHEST" in str(dot.params["precision"])


# -- the layer stack ---------------------------------------------------------------


def _paged_logits(params, cfg, tokens, chunk=16, prefilled=64, block_size=8):
    """Logits [len(tokens), V] of one sequence through the paged path as the
    engine takes it: ``prefilled`` tokens in chunks, the rest a token a step."""
    import jax
    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    cache = {**generate.init_paged_cache(cfg, 40, block_size), generate.MOE_COUNTS: generate.init_moe_counts(cfg)}
    table = jnp.arange(1, 33, dtype=jnp.int32)[None]
    feed = jax.jit(generate.paged_decode_chunk, static_argnums=5)
    out = []
    for start in [*range(0, prefilled, chunk), *range(prefilled, len(tokens))]:
        n = chunk if start < prefilled else 1
        logits, cache = feed(params, jnp.asarray(tokens[None, start : start + n]), cache, table, jnp.asarray([start]), cfg)
        out.append(np.asarray(logits[0]))
    return np.concatenate(out)


def test_the_paged_path_is_the_references_forward_pass(model, monkeypatch):
    """Two dense and three expert layers, four streams: 64 tokens prefilled in
    chunks of 16, then 36 decode steps, every position's logits against the
    reference's one forward pass. Positions run to 100 over 32 original ones, so
    YaRN's ramp and its softmax scale are both in every number; the dense cache
    gives the same. A program whose scale is left at ``(nope + rope)^-1/2`` fails."""
    import jax
    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    tokens = _tokens(0, 100)
    want = np.asarray(jax.jit(lambda p, t: _part("reference").sequence_logits(p, t, PUBLISHED))(params, jnp.asarray(tokens)))
    assert want.std() > 0.5
    got = _paged_logits(params, cfg, tokens)
    assert np.abs(got - want).max() < 2e-4, np.abs(got - want).max()
    dense, _, _ = jax.jit(generate.prefill, static_argnums=3)(params, jnp.asarray(tokens[None]), generate.init_cache(cfg, 1, 128), cfg)
    assert np.abs(np.asarray(dense[0]) - want[-1]).max() < 2e-4
    assert cfg.rope_scaling and generate.latent_softmax_scale(cfg) == pytest.approx(40**-0.5 * (0.1 * np.log(8) + 1) ** 2)
    monkeypatch.setattr(generate, "latent_softmax_scale", lambda cfg: (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)
    jax.clear_caches()
    unscaled = _paged_logits(params, cfg, tokens)
    assert np.abs(unscaled - want).max() > 0.05, np.abs(unscaled - want).max()


def test_the_rotary_tables_are_yarns():
    """``_rope_tables`` under the published numbers (64 over 4096, beta 32 / 1,
    64 rotary columns): pairs 0..10 turn as published, 23..31 a 64th as fast,
    the ramp between; without scaling nothing moves. The softmax scale is
    ``192^-1/2 x 2.00474``."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, _rope_tables, latent_softmax_scale

    yarn = dict(factor=64, original_max_position_embeddings=4096, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
    cfg = TransformerConfig(kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_scaling=yarn)
    positions = jnp.asarray([[1, 5000]])
    plain, _ = _rope_tables(positions, 64, 10000.0)
    cos, _ = _rope_tables(positions, 64, 10000.0, cfg.rope_scaling)
    freq = 10000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = np.cos(np.asarray([[1], [5000]], np.float32) * (freq * (1 - ramp) + freq / 64 * ramp).astype(np.float32))
    assert np.allclose(np.asarray(cos[0]), want, atol=2e-3)  # float32 angles of thousands of radians
    assert np.allclose(np.asarray(cos[0, :, :11]), np.asarray(plain[0, :, :11])) and not np.allclose(np.asarray(cos[0, 1, 11:]), np.asarray(plain[0, 1, 11:]), atol=1e-2)
    assert latent_softmax_scale(cfg) == pytest.approx(0.144680, abs=1e-6)
    assert latent_softmax_scale(TransformerConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64)) == 192**-0.5


def test_the_walk_over_the_pool_runs_at_yarns_scale(model, monkeypatch):
    """A decode step that reads the latent pool in place (the Pallas kernel,
    interpreted here) gives the logits of the step that gathers a view, four
    streams and YaRN's scale in both, rows at positions past the original range."""
    import jax
    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    rows = [_tokens(5, 70), _tokens(6, 41)]
    cache = {**generate.init_paged_cache(cfg, 40, 8), generate.MOE_COUNTS: generate.init_moe_counts(cfg)}
    tables = jnp.stack([jnp.arange(1, 17), jnp.arange(17, 33)]).astype(jnp.int32)
    for b, row in enumerate(rows):  # each row's prompt but its last token, as one chunk each
        fed = np.zeros((1, 80), np.int32)
        fed[0, : len(row) - 1] = row[:-1]
        _, cache = generate.paged_decode_chunk(params, jnp.asarray(fed), cache, tables[b : b + 1], jnp.asarray([0]), cfg, valid_to=jnp.asarray([len(row) - 1]))
    last = jnp.asarray([row[-1] for row in rows])
    pos = jnp.asarray([len(row) - 1 for row in rows])
    step = lambda: generate.paged_decode_step(params, last, dict(cache), tables, pos, cfg)[0]  # noqa: E731
    view = step()
    assert not generate.kernel_reads(cfg, True, 1)
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    assert generate.latent_kernel_reads(cfg, True, 1)
    walked = step()
    assert float(jnp.abs(walked - view).max()) < 2e-4 and float(jnp.abs(view).max()) > 1
    want = jax.jit(lambda p, t: _part("reference").sequence_logits(p, t, PUBLISHED))(params, jnp.asarray(rows[0]))[-1]
    assert float(jnp.abs(walked[0] - want).max()) < 2e-4


# -- the engine ------------------------------------------------------------------


def test_the_engine_serves_it_and_says_what_it_runs(model):
    """``LLMEngine`` over the latent pool of a four-stream model: greedy tokens
    are ``generate``'s, ``stats()`` names the streams and the scale in use, and
    a pass's record says how many tokens its chunk's row already held."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import generate
    from ray_tpu.serve.llm import LLMEngine, stats

    params, cfg = model
    eng = LLMEngine(params, cfg, num_slots=2, block_size=8, max_model_len=256, prefill_chunk=16)
    try:
        prompts = [_tokens(1, 75).tolist(), _tokens(2, 9).tolist()]
        got = [r.result(timeout=300) for r in [eng.submit(p, max_new_tokens=8) for p in prompts]]
        for prompt, tokens in zip(prompts, got):
            assert tokens == generate(params, jnp.asarray([prompt]), cfg, max_new_tokens=8)[0].tolist()
        st = eng.stats()
        assert st["residual_streams"] == 4 and st["latent_softmax_scale"] == pytest.approx(40**-0.5 * (0.1 * np.log(8) + 1) ** 2)
        assert st["kv_pool_not_donated"] == 0 and st["host_logit_rows"] == 0
        names = stats.ITERATION_FIELDS
        records = [dict(zip(names, r)) for r in eng.spans.iterations.since()]
        held = sorted(r["chunk_context_tokens"] for r in records if r["prefill_tokens"])
        assert held == [0, 0, 16, 32, 48, 64] and all(r["chunk_context_tokens"] == 0 for r in records if not r["prefill_tokens"])
    finally:
        eng.shutdown()


def test_an_engine_without_streams_says_one():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=48, dtype=jnp.float32)
    eng = LLMEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, num_slots=2, block_size=8, max_model_len=32, prefill_chunk=8)
    try:
        st = eng.stats()
        assert st["residual_streams"] == 1 and "latent_softmax_scale" not in st
    finally:
        eng.shutdown()


# -- what refuses the new fields ----------------------------------------------------


def test_training_refuses_the_new_fields_by_name(model):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, forward_hidden, make_train_step

    params, cfg = model
    with pytest.raises(NotImplementedError, match=r"latent attention.*hc_mult"):
        forward_hidden(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="hc_mult"):
        make_train_step(cfg, None)
    # Since PR 50 scaled rotary frequencies train under ORDINARY attention (tests/test_moe_training.py:
    # "rope_scaling" trains, and agrees with the benchmark's reference of the ``mellum`` layer); here they
    # come with latent attention, which is what is refused.
    assert "hc_mult" in cfg.inference_only and "latent attention" in cfg.inference_only and "rope_scaling" not in cfg.inference_only
    assert not TransformerConfig().inference_only


@pytest.mark.parametrize("over, named", [
    (dict(hc_mult=2), "hc_mult"),
    (dict(rope_scaling=dict(PUBLISHED["rope_scaling"]), n_layers=2, sliding_window=8, layer_kinds=("window", "window")), "rope_scaling"),
    (dict(hc_mult=2, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20,
          layer_kinds=("full", "full"), n_layers=2), "layer_kinds"),
    (dict(rope_scaling=dict(PUBLISHED["rope_scaling"], type="linear"), kv_lora_rank=16), "yarn"),
    (dict(rope_scaling=dict(factor=8.0), kv_lora_rank=16), "beta_fast"),
])
def test_a_configuration_that_has_not_run_is_refused_by_name(over, named):
    """Hyper-connections ran with latent attention and without a layer pattern;
    YaRN under a pattern scales the full layers, so a pattern without any has
    nothing for it to scale (since PR 50 it runs under ordinary attention too);
    anything else is refused as the configuration is made, not computed wrongly."""
    from ray_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError, match=named):
        TransformerConfig(**over)


@pytest.mark.parametrize("how", ["role", "cluster_prefix", "kv_import"])
def test_the_kv_transfer_plane_refuses_the_model(model, how):
    """A model with hyper-connections holds a latent pool (``hc_mult`` runs with
    latent attention only): ``role``, ``cluster_prefix`` and ``kv_import`` are
    refused as for every latent pool, by the payload they would need."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    settings = dict(num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16)
    if how == "kv_import":
        eng = LLMEngine(params, cfg, **settings)
        try:
            with pytest.raises(ValueError, match="kv_import.*latent"):
                eng.submit([1, 2, 3], max_new_tokens=2, kv_import={"oid": "x"})
        finally:
            eng.shutdown()
        return
    with pytest.raises(ValueError, match="latent"):
        LLMEngine(params, cfg, **settings, **({"role": "prefill"} if how == "role" else {"cluster_prefix": True}))
