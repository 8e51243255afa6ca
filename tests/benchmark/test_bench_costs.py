"""The operation and byte counts against hand arithmetic at the cells' shapes."""

import pytest

from benchmarks.harness import costs, peaks, registry

M = dict(
    hidden_size=4096, num_attention_heads=32, num_key_value_heads=8, intermediate_size=14336,
    vocab_size=32000, sliding_window=4096,
)
TRAIN2 = dict(M, num_hidden_layers=2)
SERVE16 = dict(M, num_hidden_layers=16)


def test_the_configuration_files_hold_the_published_widths(manifest):
    for cfg in manifest["configs"]:
        held = registry.load_cell(manifest, next(
            w["name"] for w in manifest["workloads"] if w["config"] == cfg["name"]))["config"]
        for key, value in M.items():
            assert held[key] == value, (cfg["name"], key)
        assert held["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
        assert held["source"] == cfg["source"]


def test_parameters_per_layer_and_in_all():
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; three of 4096 x 14336.
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert costs.matmul_params_per_layer(TRAIN2) == per_layer == 218_103_808
    # two norms a layer, embedding and head, the final norm.
    assert costs.n_params(TRAIN2) == 2 * (per_layer + 2 * 4096) + 2 * 32000 * 4096 + 4096
    assert round(costs.n_params(TRAIN2) / 1e9, 2) == 0.70
    assert round(costs.n_params(SERVE16) / 1e9, 2) == 3.75


def test_a_train_step_needs_14_7_tflop():
    T = 4096
    matmul = 6 * T * (2 * 218_103_808 + 4096 * 32000)
    pairs = T * (T + 1) // 2  # the 4096 window cuts nothing at T = 4096
    attention = 3 * 2 * (4 * 128 * 32 * pairs)
    assert costs.train_step_flops(TRAIN2, T, 1) == matmul + attention
    assert round(costs.train_step_flops(TRAIN2, T, 1) / 1e12, 1) == 14.8
    assert costs.train_step_flops(TRAIN2, T, 4) == 4 * costs.train_step_flops(TRAIN2, T, 1)


@pytest.mark.parametrize(
    "T, window, pairs",
    [(4, 0, 10), (4, 2, 7), (4, 4, 10), (4, 9, 10), (8192, 4096, 4096 * 4097 // 2 + 4096 * 4096)],
)
def test_causal_pairs_with_a_window(T, window, pairs):
    assert costs.causal_pairs(T, window) == pairs


def test_a_token_of_cache_is_64_kib_and_the_weights_7_5_gb():
    assert costs.kv_bytes_per_token(SERVE16) == 2 * 16 * 8 * 128 * 2 == 64 * 1024
    assert round(costs.weight_bytes(SERVE16) / 1e9, 1) == 7.5
    # A decode step reads every matmul weight and the head, not the embedding table.
    weights = (16 * 218_103_808 + 4096 * 32000) * 2
    assert costs.decode_step_bytes(SERVE16, 0) == weights
    assert costs.decode_step_bytes(SERVE16, 1000) == weights + 1000 * 65536


def test_an_unknown_device_is_an_error_not_a_default():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
