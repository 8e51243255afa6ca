"""Tests for the AlphaStar league trainer (league self-play genre).

Mirrors the reference's alpha_star tests in spirit: the machinery check is
that a league slot trained against an exploitable scripted opponent learns
to beat it (PFSP routes matches there), that exploiters train against the
live main, and that winning mains get frozen into a growing league.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.env.two_player import (
    RPS_PAYOFF,
    TwoPlayerMatrixEnv,
    scripted_biased_policy,
)


@pytest.fixture(scope="module")
def ray_cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def test_two_player_env_zero_sum():
    env = TwoPlayerMatrixEnv({"rounds": 5})
    oa, ob = env.reset()
    assert oa.shape == (6,) and not oa.any()
    total_a = total_b = 0.0
    for _ in range(5):
        oa, ob, ra, rb, done = env.step(0, 2)  # rock beats scissors
        assert ra == 1.0 and rb == -1.0
        total_a += ra
        total_b += rb
    assert done and total_a == -total_b == 5.0
    # Observations are mirrored: each side sees [mine, theirs].
    assert oa[0] == 1.0 and oa[3 + 2] == 1.0
    assert ob[2] == 1.0 and ob[3 + 0] == 1.0


@pytest.mark.slow  # a learning curve: 30 league iterations, ~40 s; the next test is its quick case
def test_alpha_star_league_learns_and_grows(ray_cluster):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib import AlphaStarConfig

    # A rock-heavy scripted player seeds the league: PFSP must route the
    # main agent's matches to it (hard at first), and the main must learn
    # the counter (paper) to a dominant win-rate.
    rocky = scripted_biased_policy(3, favorite=0, p=0.8, seed=1)
    cfg = (
        AlphaStarConfig()
        .environment(TwoPlayerMatrixEnv, env_config={"rounds": 24})
        .training(
            lr=5e-3, entropy_coeff=0.003, episodes_per_slot=6,
            self_play_fraction=0.2, snapshot_interval=8,
            snapshot_min_winrate=0.55, model_hiddens=(32,),
            scripted_league_seeds=[("rocky", rocky)],
        )
        .debugging(seed=0)
    )
    algo = cfg.build()
    algo.setup(cfg.to_dict())
    try:
        for _ in range(30):
            r = algo.step()
        # 1) The main agent exploits the biased seed decisively.
        wr = algo.winrate_vs("rocky", "main", episodes=20)
        assert wr >= 0.8, f"main failed to exploit the biased opponent (wr={wr})"
        # 2) Winning mains were frozen into the league.
        assert r["league_size"] > 1, "no snapshots were added to the league"
        # 3) All three slot kinds trained (finite losses, win-rates logged).
        for slot in ("main", "main_exploiter_0", "league_exploiter_0"):
            assert np.isfinite(r[f"{slot}/loss"])
            assert 0.0 <= r[f"{slot}/winrate"] <= 1.0
        ckpt = algo.save_checkpoint()
        algo.load_checkpoint(ckpt)
        # Reloaded main still beats the seed.
        assert algo.winrate_vs("rocky", "main", episodes=10) >= 0.7
    finally:
        algo.cleanup()


def test_alpha_star_two_iterations_train_every_slot_and_restore(ray_cluster):
    """The learning run's stand-in in tier-1: after two league iterations every
    kind of slot has trained (finite losses, win-rates logged), the league still
    holds its scripted seed alone, and a checkpoint restores main's actions."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from rllib_quick import two_iterations_then_a_restored_twin

    from ray_tpu.rllib import AlphaStarConfig

    rocky = scripted_biased_policy(3, favorite=0, p=0.8, seed=1)
    cfg = (
        AlphaStarConfig()
        .environment(TwoPlayerMatrixEnv, env_config={"rounds": 24})
        .training(
            lr=5e-3, entropy_coeff=0.003, episodes_per_slot=6,
            self_play_fraction=0.2, snapshot_interval=8,
            snapshot_min_winrate=0.55, model_hiddens=(32,),
            scripted_league_seeds=[("rocky", rocky)],
        )
        .debugging(seed=0)
    )
    slots = ("main", "main_exploiter_0", "league_exploiter_0")
    with two_iterations_then_a_restored_twin(cfg, [f"{s}/loss" for s in slots], 6) as (r, algo, twin):
        assert all(0.0 <= r[f"{s}/winrate"] <= 1.0 for s in slots)
        assert r["league_size"] == 1  # no snapshot before snapshot_interval
        assert 0.0 <= twin.winrate_vs("rocky", "main", episodes=4) <= 1.0
