"""Latent (MLA) attention over a cache and dropless routed experts (PR 32):
the serving path of ``models/generate.py`` against the plain float32 reference
of ``benchmarks/architectures/Glm4MoeLiteForCausalLM`` at a toy size, seeded
weights, on the CPU; the routing rules one at a time; the layer stacks; and
that the training path refuses such a configuration by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import registry
from ray_tpu.models import transformer
from ray_tpu.models.generate import (
    MOE_COUNTS,
    _cached_layers,
    _latent_attention,
    _latent_row_width,
    _project_latent,
    decode_step,
    generate,
    init_cache,
    init_moe_counts,
    init_paged_cache,
    paged_decode_chunk,
    paged_decode_step,
    prefill,
)
from ray_tpu.models.transformer import TransformerConfig, init_params, param_logical_axes
from ray_tpu.parallel.moe import routed_experts

ARCH = {"name": "these tests", "architecture": "Glm4MoeLiteForCausalLM", "bench_dir": registry.BENCH_DIR}
# The published keys at a toy size (tests/benchmark/toy_sizes/ holds the widths the harness's tests use).
TOY = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
    vocab_size=128, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    routed_scaling_factor=1.8, first_k_dense_replace=1, rms_norm_eps=1e-5, rope_theta=1000000,
    tie_word_embeddings=False, torch_dtype="float32", n_group=1, topk_group=1, norm_topk_prob=True,
    topk_method="noaux_tc", rope_scaling=None, partial_rotary_factor=1, attention_bias=False, hidden_act="silu",
)


@pytest.fixture(scope="module")
def reference():
    return registry.load_architecture(ARCH, "reference")


def _cfg(**over):
    model = registry.load_architecture(ARCH, "config").model_config(dict(TOY, **over), 128, "float32")
    model.update(dtype=jnp.float32, param_dtype=jnp.float32)
    return TransformerConfig(**model)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, init_params(jax.random.PRNGKey(32), cfg)


def _reference_logits(reference, params, tokens, toy=TOY):
    return np.asarray(reference.sequence_logits(params, tokens, toy))


def test_the_parameters_are_two_stacks_and_the_axes_name_every_leaf(model):
    cfg, params = model
    assert set(params) == {"embed", "dense_layers", "layers", "norm_f", "lm_head"}
    assert params["dense_layers"]["wi"].shape == (1, 64, 96) and "gate" not in params["dense_layers"]
    layers = params["layers"]
    assert layers["wi_e"].shape == (2, 8, 64, 32) and layers["wo_e"].shape == (2, 8, 32, 64)
    assert layers["wi_s"].shape == (2, 64, 32) and "wi" not in layers
    assert layers["wq_a"].shape == (2, 64, 24) and layers["wq_b"].shape == (2, 24, 4 * 20)
    assert layers["wkv_a"].shape == (2, 64, 24) and layers["wkv_b"].shape == (2, 16, 4 * 32)
    assert layers["wo"].shape == (2, 80, 64) and "wk" not in layers
    # the router is float32 whatever the weights are
    bf16 = init_params(jax.random.PRNGKey(0), dataclasses.replace(cfg, param_dtype=jnp.bfloat16))
    assert bf16["layers"]["gate"].dtype == bf16["layers"]["gate_bias"].dtype == jnp.float32
    assert bf16["layers"]["wi_e"].dtype == bf16["dense_layers"]["wq_a"].dtype == jnp.bfloat16
    assert float(jnp.abs(bf16["layers"]["gate_bias"]).min()) > 0  # small and not zero
    axes = param_logical_axes(cfg)
    shapes = jax.tree.map(lambda a: len(a.shape), params)
    assert jax.tree.map(len, axes, is_leaf=lambda a: isinstance(a, tuple)) == shapes


def test_prefill_in_chunks_then_paged_decode_equals_the_references_full_forward(model, reference):
    cfg, params = model
    tokens = [int(t) for t in np.random.RandomState(1).randint(0, 128, size=45)]
    want = _reference_logits(reference, params, tokens)
    assert want.std() > 0.3
    pool = init_paged_cache(cfg, 9, 8)
    assert set(pool) == {"ckv"} and pool["ckv"].shape == (3, 9, 8, 128)  # 16 + 8 values, padded to the lanes
    table = jnp.asarray([[5, 2, 7, 1, 3, 8]], jnp.int32)  # out of order, as an engine hands them out
    got = []
    for start in (0, 16):  # two chunks of 16, the second's tail is padding beyond valid_to
        fed = jnp.asarray([tokens[start:start + 16]], jnp.int32)
        logits, pool = paged_decode_chunk(
            params, fed, pool, table, jnp.asarray([start], jnp.int32), cfg, valid_to=jnp.asarray([30], jnp.int32)
        )
        got.append(np.asarray(logits[0]))
    got = np.concatenate(got)[:30]
    for pos in range(30, 45):  # then one token a step
        logits, pool = paged_decode_step(
            params, jnp.asarray([tokens[pos]], jnp.int32), pool, table, jnp.asarray([pos], jnp.int32), cfg
        )
        got = np.concatenate([got, np.asarray(logits)])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # the dense cache (generate()) holds the same rows and gives the same logits
    last, dense, _ = prefill(params, jnp.asarray([tokens]), init_cache(cfg, 1, 48), cfg)
    np.testing.assert_allclose(np.asarray(last[0]), want[-1], atol=2e-4, rtol=0)
    assert set(dense) == {"ckv"} and dense["ckv"].shape == (3, 1, 48, 128)
    greedy = generate(params, jnp.asarray([tokens]), cfg, max_new_tokens=3)
    assert int(greedy[0, 0]) == int(want[-1].argmax())


def test_absorbed_attention_equals_expanded_attention(model, reference):
    """The program scores the cached latent as it lies; the reference expands
    keys and values per head. One function, two forms."""
    cfg, params = model
    T = 21
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, 64))
    positions = jnp.arange(T)[None]
    lp = jax.tree.map(lambda a: a[1], {k: v for k, v in params["layers"].items() if not k.endswith("_e")})
    q, rows = _project_latent(lp, x, positions, cfg)
    assert q.shape == (1, T, 4, 128) and rows["ckv"].shape == (1, T, 128) == (1, T, _latent_row_width(cfg))
    assert not np.asarray(rows["ckv"][..., 24:]).any() and not np.asarray(q[..., 24:]).any()
    mask = positions[:, :, None] >= positions[:, None, :]
    absorbed = _latent_attention(lp, q, rows, mask, cfg) @ lp["wo"]
    w = reference._take(params["layers"], reference.ATTENTION_LEAVES, 1)
    with jax.default_matmul_precision("highest"):
        expanded = reference.attention(w, x[0], positions[0], TOY, head_group=2)
    np.testing.assert_allclose(np.asarray(absorbed[0]), np.asarray(expanded), atol=2e-5, rtol=0)


def _own_choices(reference, params, tokens):
    """The reference's own top-2 of every token in both expert layers: sets [T] of 2, a layer."""
    out = []
    x = params["embed"][jnp.asarray(tokens)]
    positions = jnp.arange(len(tokens))
    with jax.default_matmul_precision("highest"):
        x = reference.dense_layer(params["dense_layers"], 0, x, positions, TOY)
        for index in range(2):
            w = reference._take(params["layers"], reference.ATTENTION_LEAVES, index)
            h = reference.rms_norm(x + reference.attention(w, x, positions, TOY), w["mlp_norm"], 1e-5)
            _, biased = reference.biased_scores(reference._take(params["layers"], reference.ROUTER_LEAVES, index), h)
            out.append(np.sort(np.argsort(np.asarray(biased), axis=-1)[:, -2:], axis=-1))
            x = reference.expert_layer(params["layers"], index, x, positions, TOY)
    return np.stack(out, axis=1)  # [T, 2 layers, 2]


def test_the_serving_check_without_a_serving_engine_is_the_whole_forward_pass(model, reference):
    cfg, params = model
    tokens = [int(t) for t in np.random.RandomState(1).randint(0, 128, size=45)]
    rows = list(range(28, 44))
    whole = _reference_logits(reference, params, tokens)[rows]
    plain = np.asarray(reference.make_layerwise_logits(TOY)(params, tokens, rows))
    np.testing.assert_allclose(plain, whole, atol=2e-5, rtol=0)


def test_the_reference_admits_the_systems_experts_only_within_the_tie(reference):
    """Scores 0.9, 0.8, 0.5, 0.5 - d, ...: the system's {0, 1, 3} for the reference's {0, 1, 2}."""
    tie, m = reference.ROUTER_TIE, dict(n_routed_experts=6, num_experts_per_tok=3, routed_scaling_factor=1.0)
    def weights(d, served):
        s = np.array([[0.9, 0.8, 0.5, 0.5 - d, 0.2, 0.1]], np.float32)
        logit = np.log(s / (1 - s))
        w = {"w_router": jnp.asarray(logit), "router_bias": jnp.zeros(6)}
        return np.asarray(reference.routing_weights(w, jnp.ones((1, 1)), m, None if served is None else jnp.asarray([served])))[0]
    own = weights(tie / 2, None)
    assert (own > 0).tolist() == [True, True, True, False, False, False]
    near = weights(tie / 2, [3, 0, 1])
    assert (near > 0).tolist() == [True, True, False, True, False, False]  # admitted, weighed by the reference's scores
    np.testing.assert_allclose(near[[0, 1, 3]], np.array([0.9, 0.8, 0.5 - tie / 2]) / (2.2 - tie / 2), rtol=1e-5)
    np.testing.assert_allclose(weights(2 * tie, [3, 0, 1]), weights(2 * tie, None))  # too far under: its own stand
    np.testing.assert_allclose(weights(tie / 2, [-1, -1, -1]), own)  # no answer: its own
    np.testing.assert_allclose(weights(tie / 2, [0, 1, 5]), own)  # a wrong expert is not excused by a right one


def test_a_cache_keeps_each_tokens_experts_beside_its_row(model, reference):
    """``MOE_CHOICE``: the words a prefill and the decode steps after it leave are the
    experts the reference chooses (float32 here: no near-tie decides otherwise)."""
    from ray_tpu.models.generate import MOE_CHOICE, init_moe_choice, unpack_experts

    cfg, params = model
    tokens = np.random.RandomState(3).randint(0, 128, size=(2, 23))
    cache = {**init_cache(cfg, 2, 32), MOE_CHOICE: init_moe_choice(cfg, 2, 32)}
    _, cache, pos = prefill(params, jnp.asarray(tokens[:, :20]), cache, cfg)
    for t in range(20, 23):
        _, cache = decode_step(params, jnp.asarray(tokens[:, t]), cache, pos, cfg)
        pos = pos + 1
    kept = np.sort(unpack_experts(np.asarray(cache[MOE_CHOICE]), cfg), axis=-1)  # [2 layers, B, S, k]
    for b in range(2):
        want = _own_choices(reference, params, tokens[b].tolist())
        assert (kept[:, b, :23].transpose(1, 0, 2) == want).all()
    assert not kept[:, :, 23:].any()  # rows no token reached stay as made
    # A choice one int32 word cannot hold (4 ids of 9 bits) takes two, on a leading axis (PR 35;
    # until then it was refused). tests/test_serve_llm_pattern.py round-trips 8 of 128.
    assert init_moe_choice(_cfg(n_routed_experts=512, num_experts_per_tok=4), 2, 32).shape == (2, 2, 2, 32)
    assert init_moe_choice(_cfg(n_routed_experts=512, num_experts_per_tok=3), 2, 32).shape == (2, 2, 32)


def _experts(key, N=12, D=16, E=8, F=24):
    ks = jax.random.split(key, 6)
    params = dict(
        gate=jax.random.normal(ks[0], (D, E)) * D**-0.5, gate_bias=jnp.zeros((E,)),
        wg_e=jax.random.normal(ks[1], (E, D, F)) * D**-0.5, wi_e=jax.random.normal(ks[2], (E, D, F)) * D**-0.5,
        wo_e=jax.random.normal(ks[3], (E, F, D)) * F**-0.5,
    )
    return params, jax.random.normal(ks[4], (N, D))


def _expert(params, e, x):
    return (jax.nn.silu(x @ params["wg_e"][e]) * (x @ params["wi_e"][e])) @ params["wo_e"][e]


def test_the_bias_chooses_and_does_not_weigh():
    params, x = _experts(jax.random.PRNGKey(3))
    s = jax.nn.sigmoid(x @ params["gate"])
    plain, *_ = routed_experts(params, x, k=2, scale=1.8)
    # A bias that lifts experts 6 and 7 over every other: all tokens go there ...
    biased = dict(params, gate_bias=jnp.zeros((8,)).at[6:].set(5.0))
    out, sent, *_ = routed_experts(biased, x, k=2, scale=1.8)
    assert sent.tolist() == [0] * 6 + [12, 12]
    # ... weighted by their sigmoid scores alone, normalised, times the scale.
    w = 1.8 * s[:, 6:] / s[:, 6:].sum(-1, keepdims=True)
    want = w[:, :1] * _expert(params, 6, x) + w[:, 1:] * _expert(params, 7, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert not np.allclose(np.asarray(out), np.asarray(plain), atol=1e-3)
    # A bias that changes no choice changes nothing.
    same, *_ = routed_experts(dict(params, gate_bias=jnp.full((8,), 0.3)), x, k=2, scale=1.8)
    np.testing.assert_allclose(np.asarray(same), np.asarray(plain), atol=1e-6)


@pytest.mark.parametrize("tokens", [12, 200])
def test_every_token_routed_to_one_expert_loses_none(tokens):
    """No capacity: all tokens on experts 3 (and 5) are all computed, where the
    Switch layer at its training capacity drops most of them."""
    params, x = _experts(jax.random.PRNGKey(4), N=tokens)
    params["gate_bias"] = jnp.zeros((8,)).at[3].set(9.0).at[5].set(8.0)
    out, sent, *_ = routed_experts(params, x, k=2, scale=1.0)
    assert sent.tolist() == [0, 0, 0, tokens, 0, tokens, 0, 0]
    s = jax.nn.sigmoid(x @ params["gate"])
    w3, w5 = s[:, 3] / (s[:, 3] + s[:, 5]), s[:, 5] / (s[:, 3] + s[:, 5])
    want = w3[:, None] * _expert(params, 3, x) + w5[:, None] * _expert(params, 5, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(out).min(axis=-1).max()) > 0 and not np.isnan(np.asarray(out)).any()


def test_padding_rows_reach_no_expert_and_a_stack_is_run_by_layer():
    params, x = _experts(jax.random.PRNGKey(5))
    valid = jnp.arange(12) % 3 != 0
    out, sent, *_ = routed_experts(params, x, k=2, scale=1.8, valid=valid)
    whole, all_sent, *_ = routed_experts(params, x, k=2, scale=1.8)
    assert int(sent.sum()) == 2 * 8 and int(all_sent.sum()) == 2 * 12
    assert not np.asarray(out[~valid]).any()
    np.testing.assert_allclose(np.asarray(out[valid]), np.asarray(whole[valid]), atol=1e-6)
    # Stacked [L, E, ...] leaves with a layer index: the same numbers as the layer's own leaves.
    others, _ = _experts(jax.random.PRNGKey(6))
    stacked = {n: jnp.stack([others[n], params[n], others[n]]) for n in ("wg_e", "wi_e", "wo_e")}
    by_layer, sent_l, *_ = jax.jit(
        lambda layer: routed_experts({**params, **stacked}, x, k=2, scale=1.8, layer=layer)
    )(jnp.int32(1))
    np.testing.assert_allclose(np.asarray(by_layer), np.asarray(whole), atol=1e-6)
    assert sent_l.tolist() == all_sent.tolist()


@pytest.mark.parametrize("stacked", [False, True], ids=["a layer's own leaves", "a stack run by layer"])
def test_what_the_grouped_matmul_leaves_in_a_row_of_no_group_reaches_nothing(stacked, monkeypatch):
    """``jax.lax.ragged_dot`` promises nothing about a row past the last group:
    zeros here and at GLM's and Trinity's widths, NaN at 3584 x 1024 on the v5e
    (PR 47: an inactive slot's NaN latents in the null block, which every
    gathered view holds behind its mask). The rows that are no token are
    selected away, not weighted by 0: with NaN in every such row of every
    grouped product the result is finite, those rows' are zero and a token's
    is what it was."""
    params, x = _experts(jax.random.PRNGKey(5))
    valid = jnp.arange(12) % 3 != 0
    kwargs = dict(k=2, scale=1.8, valid=valid)
    if stacked:
        others, _ = _experts(jax.random.PRNGKey(6))
        params = {**params, **{n: jnp.stack([others[n], params[n], others[n]]) for n in ("wg_e", "wi_e", "wo_e")}}
        kwargs["layer"] = jnp.int32(1)
    want, sent, *_ = routed_experts(params, x, **kwargs)
    ragged_dot, poisoned = jax.lax.ragged_dot, []

    def poisoning(a, w_e, groups):
        in_a_group = jnp.arange(a.shape[0]) < jnp.sum(groups)
        poisoned.append(int(jnp.sum(~in_a_group)))
        return jnp.where(in_a_group[:, None], ragged_dot(a, w_e, groups), jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoning)
    out, sent_p, *_ = routed_experts(params, x, **kwargs)
    assert poisoned == [2 * 4] * 3  # the four rows that are no token, twice each, in all three products
    assert np.isfinite(np.asarray(out)).all() and not np.asarray(out[~valid]).any()
    np.testing.assert_array_equal(np.asarray(out[valid]), np.asarray(want[valid]))
    assert sent_p.tolist() == sent.tolist()


_KERNEL_CASES = {
    "a layer's own leaves, gated": dict(),
    "a stack run by layer, padding rows at the tail": dict(stacked=True, padded=True),
    "half the experts held": dict(share=(0, 2)),
    # an expert width that fills no whole lanes, as Nemotron's 1856: the up projection goes in transposed
    "relu^2 experts of a ragged width, the other half of a stack, padding rows": dict(stacked=True, padded=True, share=(1, 2), gated=False, F=192),
}


@pytest.mark.parametrize("case", _KERNEL_CASES)
def test_the_grouped_kernel_gives_what_ragged_dot_and_a_plain_loop_give(case, monkeypatch):
    """A prefill chunk's rows on a TPU run ``ops/grouped_matmul.py``'s kernel
    (PR 49; here interpreted: the chooser is told it is on a TPU, the kernel's
    module is not). 192 rows x top-2 of 8 experts are 48 rows a group and three
    row tiles of 128; a bias sends every row to expert 3 first (a group of 192
    rows: larger than a tile) and none to expert 5 (an empty group); the rows of
    no group (padding, experts not held) lie behind the last. bfloat16 operands
    as served: equal to ``jax.lax.ragged_dot``'s path and to a float32 loop over
    the rows to bfloat16's rounding; routing and counts the same."""
    import importlib

    from ray_tpu.ops import grouped_matmul

    opts = dict(dict(stacked=False, padded=False, share=(0, 1), gated=True, F=256), **_KERNEL_CASES[case])
    N, D, E, F, k = 192, 128, 8, opts["F"], 2
    held = E // opts["share"][1]
    params, x = _experts(jax.random.PRNGKey(49), N=N, D=D, E=E, F=F)
    params["gate_bias"] = jnp.zeros((E,)).at[3].set(9.0).at[5].set(-9.0)
    first = opts["share"][0] * held
    leaves = {n: params[n][first : first + held].astype(jnp.bfloat16) for n in ("wg_e", "wi_e", "wo_e") if opts["gated"] or n != "wg_e"}
    params = {"gate": params["gate"], "gate_bias": params["gate_bias"], **leaves}
    x = x.astype(jnp.bfloat16)
    real = N - 40 if opts["padded"] else N
    kwargs = dict(k=k, scale=1.8, share=opts["share"], valid=jnp.arange(N) < real if opts["padded"] else None)
    if opts["stacked"]:
        others, _ = _experts(jax.random.PRNGKey(6), N=N, D=D, E=E, F=F)
        params.update({n: jnp.stack([others[n][:held].astype(jnp.bfloat16), params[n], params[n] * 0]) for n in leaves})
        kwargs["layer"] = jnp.int32(1)
    want, sent, chosen, _ = routed_experts(params, x, **kwargs)
    by_expert = dict(zip(range(first, first + held), sent.tolist()))  # of the experts held here
    assert by_expert.get(3, real) == real and by_expert.get(5, 0) == 0 and sum(by_expert.values()) <= real * k

    calls = []
    kernel = grouped_matmul.grouped_matmul
    monkeypatch.setattr(grouped_matmul, "grouped_matmul", lambda *a, **kw: calls.append(a[0].shape) or kernel(*a, **kw))
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    out, sent_k, chosen_k, _ = routed_experts(params, x, **kwargs)
    assert calls == [(N * k, D)] * (2 if opts["gated"] else 1) + [(N * k, F)]
    assert sent_k.tolist() == sent.tolist() and np.array_equal(np.asarray(chosen_k), np.asarray(chosen))

    def expert(e, row):  # expert e of all E, in float32 from the served matrices
        up = row @ leaves["wi_e"][e - first].astype(jnp.float32)
        h = jax.nn.silu(row @ leaves["wg_e"][e - first].astype(jnp.float32)) * up if opts["gated"] else jnp.square(jax.nn.relu(up))
        return h @ leaves["wo_e"][e - first].astype(jnp.float32)

    xf = x.astype(jnp.float32)
    s = jax.nn.sigmoid(xf @ params["gate"])
    plain = np.zeros((N, D), np.float32)
    for n in range(real):
        w = 1.8 * s[n, chosen[n]] / jnp.sum(s[n, chosen[n]])
        for j, e in enumerate(np.asarray(chosen[n])):
            if first <= e < first + held:
                plain[n] += np.asarray(w[j] * expert(int(e), xf[n]))
    tol = dict(atol=0.03 * float(np.abs(plain).max()), rtol=0.03)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(out, np.float32), plain, **tol)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert not np.asarray(out[real:], np.float32).any()


def _expert_cells():
    """The benchmark's SERVING cells whose configuration routes experts, by its published keys."""
    manifest = registry.load_manifest()
    configs = {w["name"]: registry.load_cell(manifest, w["name"])["config"] for w in manifest["workloads"]}
    return [name for name, config in configs.items() if {"n_routed_experts", "num_experts"} & set(config) and config["path"] == "serve"]


@pytest.mark.parametrize("cell_name", _expert_cells())
def test_a_chunks_experts_run_the_kernel_on_a_tpu_and_a_decode_steps_run_as_before(cell_name, monkeypatch):
    """``moe.experts_run`` at every serving cell's shapes, read from
    ``benchmarks/configs/``: on a TPU a prefill chunk's rows (24-32 a group) run
    the grouped kernel and a decode step's (0.5-3 a group) what they ran before
    there was one, which is also what every shape runs off a TPU. The decode
    programs are found by name and shape by the benchmark's ``moe_experts_*``.
    One cell's STEP is as wide as the kernel's threshold (PR 54: 128 rows x 4 of
    64 experts, 8 rows a group) and runs the kernel too, as its ``trace_ops`` say;
    so does the pass over 128 blocks of 4 positions (PR 56: 4,096 assignments among
    128 experts, 32 rows a group, as many as its chunk's)."""
    import importlib

    from ray_tpu.parallel.moe import experts_run, grouped_matmul_tiles

    cell = registry.load_cell(registry.load_manifest(), cell_name)
    engine = cell["config"]["deployment"]["engine"]
    model = registry.load_architecture(cell, "config").model_config(cell["config"], engine["max_model_len"], "bfloat16")
    model.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    cfg = TransformerConfig(**model)
    widths = cfg.num_experts, cfg.d_model, cfg.d_expert
    before = "ragged_dot" if grouped_matmul_tiles(cfg.d_model, cfg.d_expert) else "every_expert"
    # A step feeds a slot one position, or (PR 56: generation by diffusion over blocks) its block's.
    step, chunk = (rows * cfg.experts_per_token for rows in (engine["num_slots"] * (cfg.block_diffusion or 1), engine["prefill_chunk"]))
    wide = step / cfg.num_experts >= 8  # a step of as many rows a group as ``moe._KERNEL_ROWS_A_GROUP``
    # (PR 61: 12 picks among 512 experts and 256 identities are 12 rows an expert a chunk, the fewest served)
    assert (step / cfg.num_experts <= 3 or wide) and chunk / cfg.num_experts >= (12 if cfg.zero_experts else 24)
    assert wide == (cell_name in ("lfm9.rollout-wide", "sdar6.rollout-block"))
    # The block pass's calls bear its chunk's names and shapes: that cell names no pattern (its ``trace_ops.why``).
    named = cell["config"]["trace_ops"].get("moe_experts")
    assert (named is None) == (cell_name == "sdar6.rollout-block") and (named is None or wide == ("gmm" in named))
    assert experts_run(step, *widths) == experts_run(chunk, *widths) == before  # here, on a CPU
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"), "_on_tpu", lambda: True)
    assert experts_run(step, *widths) == ("kernel" if wide else before)
    assert experts_run(chunk, *widths) == "kernel"


def test_the_dense_layer_sits_outside_the_scan_over_the_expert_layers(model):
    cfg, params = model
    pool = init_paged_cache(cfg, 5, 8)
    pool[MOE_COUNTS] = init_moe_counts(cfg)
    table = jnp.asarray([[1, 2], [0, 0]], jnp.int32)  # the second row is an inactive slot

    def step(params, pool):
        return paged_decode_step(params, jnp.asarray([7, 0]), pool, table, jnp.asarray([3, 0]), cfg)

    jaxpr = jax.make_jaxpr(step)(params, pool)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [1, 2]  # the dense stack, then the expert stack
    # the expert matrices are not sliced by the scan: they enter it whole, as constants of its body
    whole = [v.aval.shape for v in scans[1].invars[: scans[1].params["num_consts"]]]
    assert (2, 8, 64, 32) in whole and (2, 8, 32, 64) in whole
    _, after = step(params, pool)
    counts = np.asarray(after[MOE_COUNTS])
    assert counts.shape == (2, 2, 8 + 3)  # decode steps | chunks; expert layers; E + touched, fullest, steps
    assert counts[0, :, :8].sum(axis=-1).tolist() == [2, 2]  # one live row, two experts: the inactive slot is not counted
    assert counts[0, :, 8].tolist() == [2, 2] and counts[0, :, 9].tolist() == [1, 1] and counts[0, :, 10].tolist() == [1, 1]
    assert not counts[1].any()
    # a layer's index into the pool counts through both stacks: all three layers wrote position 3 of block 1
    written = np.asarray(after["ckv"])[:, 1, 3, :24]
    assert (np.abs(written).sum(axis=-1) > 0).all() and not np.asarray(after["ckv"])[:, 2].any()


STANDARD_ATTENTION = dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0, q_lora_rank=0)


@pytest.mark.parametrize("field, what", [
    (STANDARD_ATTENTION, "a router's bias that chooses \\(router_bias\\) has no bias update rule; shared experts"),
    (dict(experts_per_token=0, num_experts=0, num_shared_experts=0, first_dense_layers=0), "latent attention"),
])
def test_the_training_path_refuses_what_it_cannot_train(model, field, what):
    import optax

    cfg, _ = model
    one = dataclasses.replace(cfg, **field)
    with pytest.raises(NotImplementedError, match=f"make_train_step cannot run this configuration: {what}"):
        transformer.make_train_step(one, optax.sgd(0.1))
    with pytest.raises(NotImplementedError, match="forward_hidden cannot run.*served through models/generate.py"):
        transformer.forward_hidden(init_params(jax.random.PRNGKey(0), one), jnp.zeros((1, 4), jnp.int32), one)
    with pytest.raises(NotImplementedError, match="latent attention.*; a router's bias that chooses"):
        transformer.loss_fn({}, {"tokens": jnp.zeros((1, 5), jnp.int32)}, cfg)
    assert TransformerConfig(num_experts=4).inference_only == ""  # the Switch layer trains


def test_dropless_routed_experts_train_and_agree_with_the_cached_forward_pass(model):
    """The case of the test above whose field gained a block (PR 50): the dropless
    routed experts, under standard attention, without a router bias, a shared
    expert or a leading dense layer (each still refused by name). The training
    block's logits are the serving path's over a cache (``prefill``: the same
    router, sort and grouped matmuls from the other caller), a step of SGD lowers
    the loss, and every leaf moves."""
    import optax

    cfg, _ = model
    trains = dataclasses.replace(cfg, **STANDARD_ATTENTION, router_bias=False, num_shared_experts=0, first_dense_layers=0, head_dim=0)
    assert trains.routed_experts and trains.inference_only == ""
    params = init_params(jax.random.PRNGKey(5), trains)
    assert "gate_bias" not in params["layers"] and set(params) == {"embed", "layers", "norm_f", "lm_head"}
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, trains.vocab_size, (2, 33), dtype=np.int32))
    logits, balance = transformer.forward(params, tokens[:, :-1], trains)
    cache = init_cache(trains, 2, 32)
    served, _, _ = prefill(params, tokens[:, :-1], cache, trains)
    np.testing.assert_allclose(np.asarray(logits[:, -1]), np.asarray(served), atol=2e-4)
    assert 0.99 < float(balance) < 1.5
    opt = optax.sgd(0.05)
    step = jax.jit(transformer.make_train_step(trains, opt))
    new, _, loss = step(params, opt.init(params), {"tokens": tokens})
    _, _, after = step(new, opt.init(new), {"tokens": tokens})
    assert float(after) < float(loss)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool(jnp.any(a != b)), params, new)))


@pytest.mark.parametrize("shape", [(50, 7), (12, 5, 6), (3, 4, 5, 6), (6,)])
def test_a_leaf_is_drawn_a_slice_at_a_time_each_from_its_own_key(shape, monkeypatch):
    """However many slices are drawn together, and over however many leading
    axes: slice i is normal(key_i) of the rest, scaled."""
    key = jax.random.PRNGKey(7)

    def by_hand(lead):
        n, rest = int(np.prod(shape[:lead])), shape[lead:]
        return jnp.stack([jax.random.normal(k, rest) * 0.5 for k in jax.random.split(key, n)]).reshape(shape)

    got = transformer._draw_normal(key, shape, 0.5, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(by_hand(1)))
    if len(shape) == 4:  # a leaf whose slices are too large is drawn over its leading axes together
        monkeypatch.setattr(transformer, "_DRAW_SLICE_MAX", 100)
        transformer._draw_normal.clear_cache()
        got = transformer._draw_normal(key, shape, 0.5, jnp.float32)
        transformer._draw_normal.clear_cache()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(by_hand(2)))
