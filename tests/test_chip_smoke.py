"""chip_smoke.py off the chip: it must refuse to run without one, and its two
phase functions must pass at a toy width (interpret-mode kernels, CPU
stand-ins for the chip) — so a chip call is never spent on a Python error."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(
    chip_smoke.MISTRAL_7B, vocab_size=512, d_model=128, n_heads=4, n_kv_heads=2,
    d_ff=256, sliding_window=64,
)


def test_no_chip_exits_nonzero_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "need 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_no_device_nodes_exits_nonzero(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setenv("RAY_TPU_NUM_TPUS", "0")
    with pytest.raises(SystemExit) as e:
        chip_smoke.preflight()
    assert "no TPU chip" in str(e.value.code)


def test_compile_cache_dir_is_the_callers_or_a_fixed_path_in_the_checkout(monkeypatch):
    from ray_tpu.util.compile_cache import (
        ENV_VAR, MIN_COMPILE_ENV_VAR, MIN_COMPILE_SECS, export_compile_cache_dir,
    )

    monkeypatch.setenv(ENV_VAR, "/somewhere/else")
    monkeypatch.setenv(MIN_COMPILE_ENV_VAR, "2")
    assert export_compile_cache_dir(chip_smoke.__file__) == "/somewhere/else"
    assert os.environ[ENV_VAR] == "/somewhere/else" and os.environ[MIN_COMPILE_ENV_VAR] == "2"
    monkeypatch.delenv(ENV_VAR)
    monkeypatch.delenv(MIN_COMPILE_ENV_VAR)
    monkeypatch.chdir("/")  # not derived from the working directory
    # Exactly this: no pid, time, temp or session component.
    assert export_compile_cache_dir(chip_smoke.__file__) == os.path.join(REPO, ".jax_cache")
    assert os.environ[ENV_VAR] == os.path.join(REPO, ".jax_cache")
    # Programs that compile in about a second (the engine's decode rungs) are persisted too.
    assert os.environ[MIN_COMPILE_ENV_VAR] == MIN_COMPILE_SECS and 0 < float(MIN_COMPILE_SECS) < 0.9


def test_a_stalled_host_is_not_a_dead_node():
    """A TPU runtime starting or stopping stalls every process on the host for
    seconds (v5e, PR 21: 7.3 s and 3.3 s). The GCS, stalled with everything
    else, must not read its own deafness as the nodes' silence: it would write
    off their actors, and a written-off actor is never told to exit — the
    trainer's worker kept the chip that way."""
    import time

    import ray_tpu
    from ray_tpu._private.rpc import EventLoopThread

    ray_tpu.init(
        num_cpus=2,
        object_store_memory=64 * 1024 * 1024,
        _system_config={"node_death_timeout_s": 1.0, "heartbeat_interval_s": 0.2},
    )
    try:
        actor = ray_tpu.remote(_Pid).remote()
        pid = ray_tpu.get(actor.pid.remote(), timeout=60)
        # GCS and raylet share this process's IO loop: block it past the timeout.
        EventLoopThread.get().loop.call_soon_threadsafe(time.sleep, 2.5)
        time.sleep(3.5)
        assert [n["state"] for n in ray_tpu.nodes()] == ["ALIVE"]
        ray_tpu.kill(actor)
        chip_smoke.wait_for_exit(pid, timeout_s=20)
    finally:
        ray_tpu.shutdown()


class _Pid:
    def pid(self):
        return os.getpid()


@pytest.fixture(scope="module")
def four_fake_chips(tmp_path_factory):
    """One cluster for the module: 4 chips' worth of TPU resource on CPU, and
    a compile cache placed from outside, as the chip machine may do."""
    import ray_tpu
    from ray_tpu.util.compile_cache import ENV_VAR

    before = os.environ.get(ENV_VAR)
    os.environ[ENV_VAR] = str(tmp_path_factory.mktemp("compile_cache"))
    ray_tpu.init(num_cpus=4, num_tpus=4, object_store_memory=128 * 1024 * 1024)
    yield os.environ[ENV_VAR]
    ray_tpu.shutdown()
    if before is None:
        del os.environ[ENV_VAR]
    else:
        os.environ[ENV_VAR] = before


def _chip_env():
    keep = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")
    return {k: os.environ.get(k) for k in keep} | {"pid": os.getpid()}


def test_workers_get_their_chip_identity_at_spawn(four_fake_chips):
    import ray_tpu

    @ray_tpu.remote
    class Holder:
        def env(self):
            return _chip_env()

    one_a, one_b = (Holder.options(num_tpus=1).remote() for _ in range(2))
    none = Holder.remote()
    env_a, env_b, env_none = ray_tpu.get(
        [one_a.env.remote(), one_b.env.remote(), none.env.remote()], timeout=120
    )
    # A subset of the host: distinct chips, libtpu bounds for one chip. The
    # platform list the caller pinned (cpu, for this suite) is left alone.
    assert {env_a["TPU_VISIBLE_CHIPS"], env_b["TPU_VISIBLE_CHIPS"]} == {"0", "1"}
    for env in (env_a, env_b):
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1" and env["TPU_HOST_BOUNDS"] == "1,1,1"
        assert env["JAX_PLATFORMS"] == "cpu"
    # No grant, no chip: pinned to the CPU backend whatever was inherited.
    assert env_none["JAX_PLATFORMS"] == "cpu" and env_none["TPU_VISIBLE_CHIPS"] is None

    # A TASK's worker does not go back to the pool holding its chips.
    task_env = ray_tpu.get(ray_tpu.remote(_chip_env).options(num_tpus=2).remote(), timeout=120)
    assert task_env["TPU_VISIBLE_CHIPS"] == "2,3"
    assert task_env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    chip_smoke.wait_for_exit(task_env["pid"], timeout_s=30)
    for actor in (one_a, one_b, none):
        ray_tpu.kill(actor)


def test_phases_pass_at_toy_width_and_hand_the_chips_over(four_fake_chips):
    """The trainer's worker takes the whole host; the replica's one chip is
    free only once that process has exited and the raylet has seen it go."""
    train = chip_smoke.train_phase(
        dict(TOY, n_layers=2), n_chips=4, seq=128, steps=3, platform="cpu",
        kernel_check=dict(
            seq=256, heads=4, kv_heads=2, head_dim=64, windows=(0, 64), interpret=True,
        ),
    )
    assert set(train["kernel_rel_err"]) == {"window=0", "window=64"}
    assert train["compile_cache_dir"] == four_fake_chips  # the worker inherited it
    assert train["device"]["pid"] != os.getpid()
    chip_smoke.wait_for_exit(train["device"]["pid"])
    served = chip_smoke.serve_phase(
        dict(TOY, n_layers=2),
        dict(num_slots=4, block_size=16, max_model_len=128, num_blocks=33),
        prompt_lens=(40, 17, 64), new_tokens=(6, 4, 8), platform="cpu",
    )
    assert served["finished"] == 4
    assert served["device"]["pid"] not in (os.getpid(), train["device"]["pid"])
