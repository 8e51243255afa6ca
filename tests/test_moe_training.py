"""The training block for dropless routed experts under a pattern of window
and full layers (PR 50): ``parallel/moe.routed_experts`` differentiated on each
of its three paths against a dense masked sum, the shares of an expert-parallel
layer adding up, ``make_train_step`` against the benchmark's plain float32
reference of the ``mellum`` layer over two periods, the balance term and
``moe_stats`` against hand counts, the scopes in the step's text, and what the
training path still refuses, by name."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import transformer
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.parallel import moe

N, D, F, E, K = 256, 64, 32, 8, 2  # 512 assignments: four row tiles of the kernel's 128


def _layer(key=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    params = dict(
        gate=jax.random.normal(ks[0], (D, E)) * D**-0.5,
        wg_e=jax.random.normal(ks[1], (E, D, F)) * D**-0.5, wi_e=jax.random.normal(ks[2], (E, D, F)) * D**-0.5,
        wo_e=jax.random.normal(ks[3], (E, F, D)) * F**-0.5,
    )
    return params, jax.random.normal(ks[4], (N, D)).astype(dtype)


def _held(params, share):
    index, of = share
    held = E // of
    return {name: leaf[index * held : (index + 1) * held] if name.endswith("_e") else leaf for name, leaf in params.items()}


def _dense(params, x, score, share=(0, 1)):
    """The same layer with no sort and no grouped matmul: every held expert over
    every row, weighted by the row's weight for it."""
    s = moe.router_scores(x @ params["gate"], score)
    top, chosen = jax.lax.top_k(s, K)
    by_expert = jnp.sum(jax.nn.one_hot(chosen, E) * (top / top.sum(-1, keepdims=True))[..., None], axis=1)  # [N, E]
    first, held = share[0] * (E // share[1]), E // share[1]
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, params["wg_e"])) * jnp.einsum("nd,edf->enf", x, params["wi_e"])
    return jnp.einsum("enf,efd,ne->nd", h, params["wo_e"], by_expert[:, first : first + held])


@pytest.fixture
def path(request, monkeypatch):
    """Send ``routed_experts`` down one of its three paths, whatever the widths and the backend."""
    how = request.param
    monkeypatch.setattr(moe, "experts_run", lambda *a: how)
    return how


@pytest.mark.parametrize("share", [(0, 1), (1, 4)], ids=["whole", "a share"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("path", ["ragged_dot", "every_expert", "kernel"], indirect=True)
def test_the_gradient_through_each_path_is_the_dense_masked_sums(path, score, share):
    """Loss and gradients in the activations, the router and the three expert
    matrices; a share is differentiated under the bound a training step gives it
    (``held_rows``), here so tight that the held rows take several pieces."""
    params, x = _layer()
    target = jax.random.normal(jax.random.PRNGKey(9), (N, D))
    rows = None if share == (0, 1) else 128  # N k = 512 assignments in pieces of 128, where ~128 are held: one or two run
    held = _held(params, share)

    def system(p, x):
        out, sent, chosen, scores = moe.routed_experts(p, x, k=K, share=share, score=score, rows=rows)
        return jnp.sum((out - target) ** 2), (sent, chosen)

    def dense(p, x):
        return jnp.sum((_dense(p, x, score, share) - target) ** 2)

    (loss, (sent, chosen)), grads = jax.value_and_grad(system, (0, 1), has_aux=True)(held, x)
    want, want_grads = jax.value_and_grad(dense, (0, 1))(held, x)
    assert float(jnp.abs(loss - want)) < 1e-3 * float(want)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(got - ref)) <= 1e-4 * float(jnp.linalg.norm(ref)) + 1e-6
    first = share[0] * (E // share[1])
    counts = np.bincount(np.asarray(chosen).ravel(), minlength=E)
    assert np.asarray(sent).tolist() == counts[first : first + E // share[1]].tolist()


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"], indirect=True)
def test_the_shares_add_up(path):
    """At ``expert_share`` (i, 4), i = 0..3: the four parts of the layer's
    output, of the router's gradient and of the activations' gradient sum to
    the uncut layer's, and each held expert's gradient equals its own in the
    uncut layer."""
    params, x = _layer(3)
    target = jax.random.normal(jax.random.PRNGKey(5), (N, D))

    def part(p, x, share, rows):
        return moe.routed_experts(p, x, k=K, share=share, score="softmax", rows=rows)[0]

    whole_out = part(params, x, (0, 1), None)
    # The loss is linear in the layer's output, so that the parts' gradients are parts of the whole's.
    loss = lambda p, x, share, rows: jnp.sum(part(p, x, share, rows) * target)  # noqa: E731
    whole = jax.grad(loss, (0, 1))(params, x, (0, 1), None)
    outs, grads = [], []
    for i in range(4):
        held = _held(params, (i, 4))
        outs.append(part(held, x, (i, 4), 128))
        grads.append(jax.grad(loss, (0, 1))(held, x, (i, 4), 128))
    np.testing.assert_allclose(np.asarray(sum(outs)), np.asarray(whole_out), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sum(g[1] for g in grads)), np.asarray(whole[1]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sum(g[0]["gate"] for g in grads)), np.asarray(whole[0]["gate"]), atol=2e-5)
    for i, g in enumerate(grads):
        for name in ("wg_e", "wi_e", "wo_e"):
            np.testing.assert_allclose(np.asarray(g[0][name]), np.asarray(whole[0][name][2 * i : 2 * i + 2]), atol=2e-5)


def test_the_bound_is_a_share_and_a_quarter_in_whole_tiles_and_no_capacity():
    assert moe.held_rows(131072, (0, 4)) == 40960 and moe.held_rows(131072, (3, 4)) == 40960
    assert moe.held_rows(131072, (0, 1)) is None and moe.held_rows(1024, (0, 2)) == 1024 * 5 // 8
    assert moe.held_rows(128, (0, 1)) is None and moe.held_rows(64, (0, 1)) is None
    # every token sent to the held experts alone: four times the bound, and nothing dropped
    params, x = _layer(1)
    x = jnp.abs(x)
    params["gate"] = jnp.where(jnp.arange(E) < 2, 1.0, -1.0) * jnp.ones((D, 1))  # experts 0 and 1 take every token
    held = _held(params, (0, 4))
    out, sent, *_ = moe.routed_experts(held, x, k=K, share=(0, 4), score="softmax", rows=128)
    assert np.asarray(sent).tolist() == [N, N]
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(held, x, "softmax", (0, 4))), atol=2e-5)


def test_the_balance_term_is_the_hand_count():
    scores = jnp.asarray([[0.5, 0.3, 0.1, 0.1], [0.1, 0.6, 0.2, 0.1], [0.4, 0.1, 0.4, 0.1]], jnp.float32)
    chosen = jnp.asarray([[0, 1], [1, 2], [0, 2]], jnp.int32)
    term, sent = moe.balance_term(scores, chosen, "softmax")
    assert np.asarray(sent).tolist() == [2, 2, 2, 0]
    share, mean = np.array([2, 2, 2, 0]) / 6, np.array([1.0, 1.0, 0.7, 0.3]) / 3
    assert float(term) == pytest.approx(4 * float(share @ mean), rel=1e-6)
    uniform, _ = moe.balance_term(jnp.full((4, 4), 0.25), jnp.asarray([[0, 1], [2, 3], [0, 2], [1, 3]]), "softmax")
    assert float(uniform) == pytest.approx(1.0)
    # a count has no gradient; the probabilities' is the shares
    grad = jax.grad(lambda s: moe.balance_term(s, chosen, "softmax")[0])(scores)
    np.testing.assert_allclose(np.asarray(grad), np.tile(4 * share / 3, (3, 1)), rtol=1e-6)


# -- the model against the benchmark's reference ------------------------------------------------------------------

ARCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")


def _cell(periods: int, dtype: str):
    """The benchmark's ``mellum`` configuration at the toy widths of its tests,
    ``periods`` periods deep: (the cell, its file, the program's configuration)."""
    from benchmarks.harness import registry

    with open(os.path.join(ARCH, "configs", "mellum2-12b-a2.5b-train4.json")) as f:
        file = json.load(f)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark", "toy_sizes", "MellumForCausalLM.json")) as f:
        file.update(json.load(f))
    file.update(torch_dtype=dtype, num_hidden_layers=4 * periods, layer_types=file["layer_types"] * periods,
                mlp_layer_types=file["mlp_layer_types"] * periods)
    cell = {"name": "these tests", "architecture": "MellumForCausalLM", "bench_dir": registry.BENCH_DIR, "config": file}
    model = registry.load_architecture(cell, "config").model_config(file, 128, "float32")
    for key in ("dtype", "param_dtype"):
        model[key] = jnp.dtype(model[key]).type
    return cell, file, TransformerConfig(**model)


def _batch(cfg, seed):
    return {"tokens": jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, (2, 129), dtype=np.int32))}


def _against_the_reference(cell, file, cfg, params=None, seed=3):
    """(|loss - reference's|, each leaf's gradient by relative L2): one SGD step's
    parameter delta over the rate, as the benchmark's check reads it."""
    from benchmarks.harness import registry, train_cell

    params = init_params(jax.random.PRNGKey(seed), cfg) if params is None else params
    check = train_cell._reference_check(cell, cfg, None, params, _batch(cfg, seed))
    assert np.isfinite(check["loss"]) and registry.load_architecture(cell, "reference").LAYERS == "layers"
    return abs(check["loss"] - check["ref_loss"]), check["grad_rel_l2"]


def test_the_train_step_agrees_with_the_reference_in_float32_over_two_periods():
    """8 layers, so the scan over periods runs twice; a window (32) shorter than
    the sequence (128) and positions past YaRN's original 32; this chip's share
    (0, 4) of 64 experts. At equal precision no token's choice of experts flips:
    the loss and every leaf's gradient to 1e-4."""
    cell, file, cfg = _cell(2, "float32")
    assert cfg.layer_kinds == ("window", "window", "window", "full") * 2 and cfg.expert_share == (0, 4)
    assert transformer.layer_rope(cfg, "full") == cfg.rope_scaling and dict(cfg.rope_scaling)["original_max_position_embeddings"] == 32.0
    assert cfg.inference_only == "" and cfg.head_dim * cfg.n_heads != cfg.d_model and cfg.qk_norm
    gap, grads = _against_the_reference(cell, file, cfg)
    assert gap < 1e-4 and max(grads.values()) < 1e-4, (gap, grads)
    assert "layers/gate_bias" not in grads and {"layers/gate", "layers/wg_e", "layers/q_norm"} <= set(grads)


def test_in_bfloat16_the_leaves_upstream_of_no_router_stay_within_the_dense_models_tolerance():
    """bf16 activations upstream of a float32 router flip a token's 8th and 9th
    expert where they nearly tie, which no tolerance on an expert's gradient can
    tell from a fault; the embedding and the FIRST layer's query, key and value
    matrices have no router upstream of them and see a flip only through the
    backward pass: those, layer 0 sliced out of its stack, are held to the dense
    model's 0.03. (So are the head and the last norm, which every router is
    upstream of: at this size they read as low, and that is all it says.)"""
    from benchmarks.harness import registry, train_cell

    cell, file, cfg = _cell(1, "bfloat16")
    gap, grads = _against_the_reference(cell, file, cfg)
    assert gap < 0.02, gap
    for leaf in ("embed", "lm_head", "norm_f"):
        assert grads[leaf] < 0.03, (leaf, grads)
    assert max(grads.values()) < 0.5, grads  # nothing is wrong by its own size
    params, batch = init_params(jax.random.PRNGKey(3), cfg), _batch(cfg, 3)
    reference = registry.load_architecture(cell, "reference")
    got = jax.grad(lambda p: transformer.loss_fn(p, batch, cfg))(params)["layers"]
    want = jax.grad(lambda p: reference.loss(p, batch["tokens"], file))(params)["layers"]
    first = {leaf: float(train_cell._rel_l2(got[leaf][0], want[leaf][0])) for leaf in ("wq", "wk", "wv")}
    assert max(first.values()) < 0.03, first


@pytest.mark.parametrize("fault", ["the amplitude left at 1", "the full layer roped plainly", "the full layer without positions", "the window left out"])
def test_a_model_with_a_fault_in_its_positions_fails_the_reference(fault):
    cell, file, cfg = _cell(1, "float32")
    wrong = cfg
    if fault == "the amplitude left at 1":
        wrong = dataclasses.replace(cfg, rope_scaling=dict(cfg.rope_scaling, mscale=0.0))
    elif fault == "the full layer roped plainly":
        # No configuration of the program ropes a full layer plainly: the reference is told the window layers' group
        # for both kinds, and the program as it is configured (YaRN) must then disagree with it as widely.
        file = dict(file, rope_parameters=dict(file["rope_parameters"], full_attention=file["rope_parameters"]["sliding_attention"]))
        cell = dict(cell, config=file)
    elif fault == "the full layer without positions":
        wrong = dataclasses.replace(cfg, rope_scaling={})
        assert transformer.layer_rope(wrong, "full") is None
    else:
        wrong = dataclasses.replace(cfg, sliding_window=128)
    gap, grads = _against_the_reference(cell, file, wrong)
    assert max(grads.values()) > 0.01, (gap, grads)


def test_moe_stats_are_the_counts_of_one_forward_pass():
    _, _, cfg = _cell(1, "float32")
    params = init_params(jax.random.PRNGKey(1), cfg)
    batch = {"tokens": jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 65), dtype=np.int32))}
    stats = jax.jit(lambda p, b: transformer.moe_stats(p, b, cfg))(params, batch)
    sent = np.asarray(stats["assignments"])
    assert sent.shape == (4, 64) and (sent.sum(axis=1) == 2 * 64 * 8).all()
    held = sent[:, :16]
    assert float(stats["held_share"]) == pytest.approx(held.sum() / sent.sum())
    assert float(stats["fullest_over_mean"]) == pytest.approx((held.max(axis=1) / held.mean(axis=1)).max())
    # the balance term is what the loss adds, over its coefficient
    fused = dataclasses.replace(cfg, fused_loss=False)
    with_term = transformer.loss_fn(params, batch, fused)
    without = transformer.loss_fn(params, batch, dataclasses.replace(fused, balance_loss_coef=0.0))
    assert float(with_term - without) == pytest.approx(cfg.balance_loss_coef * float(stats["balance"]), rel=1e-3)
    assert 0.99 < float(stats["balance"]) < 1.3  # 1.0 where routing is uniform
    with pytest.raises(ValueError, match="no routed experts"):
        transformer.moe_stats(params, batch, TransformerConfig())


def test_the_scopes_are_in_the_steps_lowered_text():
    _, _, cfg = _cell(1, "bfloat16")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt = optax.adamw(1e-4)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 129), jnp.int32)}
    step = jax.jit(transformer.make_train_step(cfg, opt)).trace(params, jax.eval_shape(opt.init, params), batch)
    text = step.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine", "attention_window", "attention_full"):
        assert scope in text, scope
    stats = jax.jit(lambda p, b: transformer.moe_stats(p, b, cfg)).trace(params, batch).lower(lowering_platforms=("tpu",))
    assert "moe_router" in stats.as_text(debug_info=True)
    # a period's layers are unrolled inside ONE scan over the periods
    jaxpr = jax.make_jaxpr(lambda p, b: transformer.loss_fn(p, b, cfg))(params, batch)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans][:1] == [1]


# -- what a layer under remat keeps of its experts' block (PR 55) --------------------------------------------------


def _calls(jaxpr, counts):
    """How often each primitive stands in a jaxpr and every jaxpr inside it, the
    branches of a ``cond`` left out: what a step runs however its routing falls
    (``_held_rows``' pieces behind the bound are one cond)."""
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        if eqn.primitive.name != "cond":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                _calls(inner, counts)
    return counts


def _policy_without_the_experts_names():
    attention_only = (*transformer._KEPT_INPUTS, *transformer._KEPT_NORM_INPUTS, transformer.FLASH_OUT, transformer.FLASH_LSE)
    return jax.checkpoint_policies.save_only_these_names(*attention_only)


def test_the_backward_pass_of_a_layer_under_remat_runs_neither_the_router_nor_the_sort_nor_a_grouped_matmul_again(monkeypatch):
    """The mechanism's counter, on the Mellum-shaped toy (2 x 128 tokens: 2,048
    assignments under a bound of 640, so every layer takes the bounded path):
    the train step holds, a scanned layer and outside the cond, ONE ``top_k``,
    ONE sort and nine grouped matmuls: the forward's three and the six of the
    gradients. With the experts' names taken out of the policy (the parent's)
    the backward pass starts from the layer's input: two routers, two sorts,
    twelve grouped matmuls, and the gather of the chosen experts' scores
    (131,072 scalars at the cell's size) once more."""
    _, _, cfg = _cell(1, "bfloat16")
    cfg = dataclasses.replace(cfg, remat=True)
    assert moe.held_rows(2 * 128 * cfg.experts_per_token, cfg.expert_share) == 640
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt = optax.adamw(1e-4)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 129), jnp.int32)}

    def a_layer():
        step = jax.jit(transformer.make_train_step(cfg, opt)).trace(params, jax.eval_shape(opt.init, params), batch)
        assert "moe_experts" in step.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
        counts = _calls(step.jaxpr.jaxpr, {})
        return {name: counts[name] / cfg.n_layers for name in ("top_k", "sort", "ragged_dot_general", "gather")}

    kept = a_layer()
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT", _policy_without_the_experts_names())
    parents = a_layer()
    assert {name: kept[name] for name in ("top_k", "sort", "ragged_dot_general")} == {"top_k": 1, "sort": 1, "ragged_dot_general": 9}, (
        "kept: " + ", ".join(moe.KEPT_OF_A_BOUNDED_BLOCK))
    assert {name: parents[name] for name in ("top_k", "sort", "ragged_dot_general")} == {"top_k": 2, "sort": 2, "ragged_dot_general": 12}
    assert parents["gather"] - kept["gather"] == 1, (kept, parents)


@pytest.mark.parametrize("rows", [96, 16], ids=["the share fits its bound", "pieces behind the bound run"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_what_the_policy_keeps_changes_no_bit_of_the_loss_or_of_a_gradient(dtype, rows, monkeypatch):
    """A policy changes what is STORED: a step's loss and every leaf's gradient
    with the experts' names kept equal those without them bit for bit, where
    the held rows fit the bound (~64 of 128 assignments under 96) and where the
    bound is so tight (16) that the pieces behind it run. Four scanned layers of
    a half share at toy widths: the Mellum-shaped toy compiles four times as
    long and runs the same block."""
    cfg = TransformerConfig(**{**TRAINS, "dtype": dtype}, num_experts=8, experts_per_token=2, d_expert=16, router_score="softmax",
                            router_bias=False, expert_share=(1, 2), remat=True)
    monkeypatch.setattr(moe, "held_rows", lambda assignments, share: rows)
    params = init_params(jax.random.PRNGKey(3), cfg)
    batch = {"tokens": jnp.asarray(np.random.default_rng(3).integers(0, 64, (2, 33), dtype=np.int32))}
    step = lambda: jax.jit(jax.value_and_grad(lambda p: transformer.loss_fn(p, batch, cfg)))(params)  # noqa: E731
    held = np.asarray(transformer.moe_stats(params, batch, cfg)["assignments"])[:, 4:].sum(axis=1)
    assert (held > rows).all() if rows == 16 else (held <= rows).all()
    loss, grads = step()
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT", _policy_without_the_experts_names())
    want, want_grads = step()
    assert np.isfinite(float(loss)) and float(loss) == float(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        assert np.array_equal(np.asarray(got), np.asarray(ref)), jax.tree_util.keystr(path)


# -- what trains now and what is still refused --------------------------------------------------------------------

YARN = dict(factor=4, original_max_position_embeddings=8, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=0)
TRAINS = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=32, dtype=jnp.float32)


@pytest.mark.parametrize("fields", [
    dict(layer_kinds=("window", "full") * 2, sliding_window=8),
    dict(layer_kinds=("window", "full") * 2, sliding_window=8, rope_scaling=YARN),
    dict(qk_norm=True),
    dict(head_dim=16),
    dict(rope_scaling=YARN),
    dict(num_experts=8, experts_per_token=2, d_expert=16, router_score="softmax", router_bias=False),
    dict(num_experts=8, experts_per_token=2, d_expert=16, router_score="sigmoid", router_bias=False, routed_scaling_factor=1.8),
    dict(num_experts=8, experts_per_token=2, d_expert=16, router_score="softmax", router_bias=False, expert_share=(1, 2)),
], ids=["layer_kinds", "scaled_full_layers", "qk_norm", "head_dim", "rope_scaling", "routed_experts", "sigmoid_no_bias", "expert_share"])
def test_a_field_that_gained_a_block_trains(fields):
    """Each of the fields the training path refused by name before PR 50, by
    itself on the plain model: a step under SGD moves every leaf and lowers the loss."""
    cfg = TransformerConfig(**TRAINS, **fields)
    assert cfg.inference_only == ""
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 33), dtype=np.int32))}
    opt = optax.sgd(0.05)
    step = jax.jit(transformer.make_train_step(cfg, opt))
    new, _, loss = step(params, opt.init(params), batch)
    _, _, after = step(new, opt.init(new), batch)
    assert np.isfinite(float(loss)) and float(after) < float(loss)
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), params, new)
    assert all(jax.tree.leaves(moved)), moved


@pytest.mark.parametrize("fields, what", [
    (dict(num_experts=8, experts_per_token=2, d_expert=16), "a router's bias that chooses \\(router_bias\\) has no bias update rule"),
    (dict(num_experts=8, experts_per_token=2, d_expert=16, router_bias=False, num_shared_experts=1), "shared experts \\(num_shared_experts\\)"),
    (dict(num_experts=8, experts_per_token=2, d_expert=16, router_bias=False, first_dense_layers=1), "leading dense layers \\(first_dense_layers\\)"),
    (dict(num_experts=8, experts_per_token=2, d_expert=16, router_bias=False, expert_activation="relu2"), "experts without a gate matrix"),
    (dict(attn_gate=True), "gated attention \\(attn_gate\\)"),
    (dict(post_norms=True), "post-branch norms \\(post_norms\\)"),
    (dict(embed_multiplier=2.0), "an embedding multiplier"),
    (dict(kv_lora_rank=16, q_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8), "latent attention"),
])
def test_the_rest_is_still_refused_by_name(fields, what):
    cfg = TransformerConfig(**TRAINS, **fields)
    with pytest.raises(NotImplementedError, match=f"make_train_step cannot run this configuration: .*{what}"):
        transformer.make_train_step(cfg, optax.sgd(0.1))


def test_a_configuration_states_its_rotary_by_kind_in_one_place():
    pattern = dict(n_layers=2, sliding_window=8, layer_kinds=("window", "full"))
    assert transformer.layer_rope(TransformerConfig(), None) == ()
    assert transformer.layer_rope(TransformerConfig(**pattern), "window") == ()
    assert transformer.layer_rope(TransformerConfig(**pattern), "full") is None  # the ``afmoe`` layer
    scaled = TransformerConfig(**pattern, rope_scaling=YARN)
    assert transformer.layer_rope(scaled, "full") == scaled.rope_scaling and transformer.layer_rope(scaled, "window") == ()
    assert transformer.layer_rope(TransformerConfig(rope_scaling=YARN), None) == scaled.rope_scaling
    for bad, why in ((dict(pattern, layer_kinds=("window", "window"), rope_scaling=YARN), "without full layers"),
                     (dict(router_score="tanh"), "'sigmoid' or 'softmax'")):
        with pytest.raises(ValueError, match=why):
            TransformerConfig(**bad)
    # a router without a bias has no such leaf (AdamW would decay one that no gradient reaches)
    routed = dict(num_experts=8, experts_per_token=2, d_expert=16)
    assert "gate_bias" in transformer._layer_leaves(TransformerConfig(**routed), "routed")
    assert "gate_bias" not in transformer._layer_leaves(TransformerConfig(**routed, router_bias=False), "routed")


def test_a_loop_reports_its_routing_as_plain_numbers():
    """``train_loop_utils.moe_reporter``: what ``session.report`` takes (floats
    and lists), the same counts as ``moe_stats``."""
    from ray_tpu.train.jax.train_loop_utils import moe_reporter

    cfg = TransformerConfig(**TRAINS, num_experts=8, experts_per_token=2, d_expert=16, router_score="softmax", router_bias=False,
                            expert_share=(1, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 33), dtype=np.int32))}
    report = moe_reporter(cfg)(params, batch)
    assert set(report) == {"moe/balance", "moe/held_share", "moe/fullest_over_mean", "moe/assignments"}
    assert all(isinstance(report[key], float) for key in ("moe/balance", "moe/held_share", "moe/fullest_over_mean"))
    sent = np.asarray(report["moe/assignments"])
    assert sent.shape == (4, 8) and (sent.sum(axis=1) == 2 * 32 * 2).all()
    assert report["moe/held_share"] == pytest.approx(sent[:, 4:].sum() / sent.sum())
    json.dumps(report)
