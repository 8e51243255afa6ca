"""What a learning run's tier-1 stand-in asserts. A learning curve is a soak by
nature and runs under ``slow``; beside it the same file keeps one quick test of
the same algorithm on the same fixture: two iterations, finite numbers under
the keys the algorithm reports, and a checkpoint from which a SECOND instance
takes the same greedy actions for the same observations."""

import contextlib

import numpy as np


@contextlib.contextmanager
def two_iterations_then_a_restored_twin(cfg, keys, obs_dim, act=None):
    """Yields (the second iteration's result, the algorithm, its restored
    twin) for what is the algorithm's own to assert; cleans both up."""
    act = act or (lambda algo, obs: algo.compute_single_action(obs))
    algos = []
    try:
        algos.append(cfg.build())
        for _ in range(2):
            result = algos[0].step()
        for key in keys:
            assert np.isfinite(result[key]), (key, result[key])
        algos.append(cfg.build())
        algos[1].load_checkpoint(algos[0].save_checkpoint())
        for obs in np.random.default_rng(0).uniform(-1, 1, (16, obs_dim)).astype(np.float32):
            np.testing.assert_array_equal(np.asarray(act(algos[0], obs)), np.asarray(act(algos[1], obs)))
        yield result, algos[0], algos[1]
    finally:
        for algo in algos:
            algo.cleanup()

