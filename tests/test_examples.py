"""Every example in examples/ must actually run (subprocess, CPU, small).

The reference ships runnable example galleries; these are the equivalent
user-facing entry points, so breakage is a release blocker, not a docs
nit."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    ("train_transformer.py", ["2"], "final loss:"),
    ("train_moe.py", ["3"], "held share: 0."),
    ("serve_llm.py", [], "generated:"),
    ("tune_hyperparams.py", [], "best config:"),
    ("data_pipeline.py", [], "jax batches ok"),
    ("rllib_ppo.py", ["1"], "iter 0:"),
    ("cross_language_task.py", [], "wordcount:"),
    ("serve_composed.py", [], "math:"),
    ("rllib_offline.py", [], "expert agreement:"),
    ("speculative_decode.py", [], "exact-output speculative decoding ok"),
    ("cpp_native_driver.py", [], "CPP_API_PASS"),
]


@pytest.mark.parametrize("script,args,expect", EXAMPLES, ids=[e[0] for e in EXAMPLES])
def test_example_runs(script, args, expect):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        RAY_TPU_NUM_TPUS="0",
        PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
    )
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", script), *args],
            capture_output=True,
            text=True,
            timeout=560,
            env=env,
            cwd=REPO,
        )
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = (e.stderr or b"").decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
        pytest.fail(f"{script} timed out; partial stdout:\n{out}\nstderr:\n{err}")
    assert proc.returncode == 0, f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
    assert expect in proc.stdout, f"{script} output missing {expect!r}:\n{proc.stdout}"
