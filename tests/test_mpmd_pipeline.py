"""MPMD pipeline over compiled graphs (ISSUE 12).

Covers the descriptor channel plane (KIND_DEVICE envelopes through channel
slots, payloads streamed out of band — experimental/channel/
device_envelope.py) and the MPMD pipeline built on it (parallel/
mpmd_pipeline.py): zero host-store copies of activations, bit-exact parity
vs the single-controller ``pipeline_apply``, device-resident driver inputs
routed as descriptor slots instead of silently msgpack-serialized through
the ring, the doorbell short-circuiting the configurable re-poll backoff,
and the chaos path — SIGKILL of one stage surfaces a typed error naming it
and every channel slot / device buffer / pinned payload is reclaimed.
"""

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.dag import InputNode
from ray_tpu.exceptions import ActorDiedError


def _drain_resident(stats_fn, target: int, timeout: float = 30.0) -> dict:
    """Pin releases and loop-exit reclaims are asynchronous (one-way frames,
    thread joins): poll the counters down instead of sleeping blind."""
    deadline = time.monotonic() + timeout
    st = stats_fn()
    while time.monotonic() < deadline:
        st = stats_fn()
        if st["resident_count"] <= target:
            return st
        time.sleep(0.1)
    return st


def test_doorbell_wakes_backed_off_reader():
    """Satellite: channel_poll_interval_ms is a RayConfig knob and the
    doorbell path never waits a full poll interval. With the fallback
    re-poll cap cranked to 2 s, an idle resident loop's reader is deep in
    its exponential backoff — yet a fresh execute() completes in far less
    than one poll interval, because the producer's doorbell (or the device
    payload's deposit) sets the reader's gate event immediately."""
    os.environ["RAY_TPU_CHANNEL_POLL_INTERVAL_MS"] = "2000"
    try:
        ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
        from ray_tpu._private import worker_context

        cw = worker_context.get_core_worker()
        assert cw.cfg.channel_poll_interval_ms == 2000

        @ray_tpu.remote
        class Inc:
            def work(self, x):
                return x + 1

        with InputNode() as inp:
            dag = Inc.bind().work.bind(Inc.bind().work.bind(inp))
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(0).get() == 2  # warm the loops
            # Let every blocked reader back off to the 2 s cap...
            time.sleep(1.2)
            # ...then a full round trip must be doorbell-paced, not
            # poll-paced: 2 stages x 2 s would be >= 4 s on poll alone.
            t0 = time.monotonic()
            assert compiled.execute(5).get(timeout=30) == 7
            assert time.monotonic() - t0 < 1.5
        finally:
            compiled.teardown()
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_CHANNEL_POLL_INTERVAL_MS", None)


@pytest.fixture(scope="module")
def pipeline_cluster():
    ray_tpu.init(num_cpus=6, object_store_memory=192 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@ray_tpu.remote(tensor_transport="collective")
class DeviceStage:
    def work(self, x):
        import jax.numpy as jnp

        return jnp.tanh(x) + 1.0

    def devobj_stats(self):
        from ray_tpu.experimental.device_object import device_object_stats

        return device_object_stats()

    def pid(self):
        return os.getpid()


def test_device_descriptor_stream_zero_host_copy(pipeline_cluster):
    """Tentpole core: a tensor_transport actor's jax.Array result crosses a
    compiled-graph edge as a ~300 B descriptor slot while the payload rides
    the p2p direct mailbox — the host object store sees ZERO activation
    objects, the producer's pin watermark trails the ring by <= 2 slots,
    and teardown reclaims every payload (no leaked device buffers)."""
    import jax.numpy as jnp

    from ray_tpu._private import worker_context

    cw = worker_context.get_core_worker()
    s1, s2 = DeviceStage.bind(), DeviceStage.bind()
    with InputNode() as inp:
        dag = s2.work.bind(s1.work.bind(inp))
    compiled = dag.experimental_compile()
    h1, h2 = s1.resolve_actor_handle(), s2.resolve_actor_handle()
    try:
        store0 = cw.raylet.call("get_state")["store"]["num_objects"]
        x = jnp.arange(8.0, dtype=jnp.float32)
        expected = np.tanh(np.tanh(np.arange(8.0)) + 1.0) + 1.0
        iters = 6
        for _ in range(iters):
            out = compiled.execute(x).get(timeout=60)
            np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)
        assert cw.raylet.call("get_state")["store"]["num_objects"] == store0

        st = ray_tpu.get(h1.devobj_stats.remote(), timeout=30)
        # Every iteration eager-pushed stage1's activation out of band...
        assert st["chan_sends"] >= iters, st
        # ...no resolution fell back to a host-store copy...
        assert st["transfers_host"] == 0, st
        # ...and ring-advance reaping keeps the pin watermark at <= 2
        # in-flight payloads (read_count - 2 is provably-done).
        assert st["resident_count"] <= 2, st
    finally:
        compiled.teardown()
    st = _drain_resident(
        lambda: ray_tpu.get(h1.devobj_stats.remote(), timeout=30), target=0
    )
    assert st["resident_count"] == 0, st
    # Free the module cluster's CPUs for the pipeline builds below.
    ray_tpu.kill(h1)
    ray_tpu.kill(h2)


def test_driver_device_input_routed_as_descriptor(pipeline_cluster):
    """Satellite: execute() fed a device-resident jax.Array no longer
    msgpack-serializes it silently through the host ring — the driver is
    the holder and the input crosses as a descriptor slot (chan_sends
    counts it; the store object count stays flat), and teardown reclaims
    the driver's payload scope."""
    import jax.numpy as jnp

    from ray_tpu._private import worker_context
    from ray_tpu.experimental.device_object import device_object_stats

    cw = worker_context.get_core_worker()

    @ray_tpu.remote
    class SumStage:
        def total(self, x):
            return float(x.sum())

    node = SumStage.bind()
    with InputNode() as inp:
        dag = node.total.bind(inp)
    compiled = dag.experimental_compile()
    base = device_object_stats()
    try:
        store0 = cw.raylet.call("get_state")["store"]["num_objects"]
        x = jnp.ones((16,), dtype=jnp.float32)
        for _ in range(4):
            assert compiled.execute(x).get(timeout=60) == 16.0
        st = device_object_stats()
        assert st["chan_sends"] - base["chan_sends"] >= 4, (base, st)
        assert cw.raylet.call("get_state")["store"]["num_objects"] == store0
    finally:
        compiled.teardown()
    # The driver's payload scope reclaims at teardown (resident counts are
    # vs the pre-test base — this pytest process may hold other device
    # objects from earlier modules).
    st = _drain_resident(device_object_stats, target=base["resident_count"])
    assert st["resident_count"] <= base["resident_count"], (base, st)
    ray_tpu.kill(node.resolve_actor_handle())


def test_unserializable_result_is_per_iteration_error(pipeline_cluster):
    """A stage return value the serializer rejects becomes THAT iteration's
    TaskError (the DAG keeps serving) — not a resident-loop crash that
    wedges every subsequent get()."""
    import threading

    from ray_tpu.exceptions import TaskError

    @ray_tpu.remote
    class Sometimes:
        def work(self, x):
            if x == 1:
                return threading.Lock()  # pickle refuses
            return x

    node = Sometimes.bind()
    with InputNode() as inp:
        dag = node.work.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(0).get(timeout=30) == 0
        with pytest.raises(TaskError):
            compiled.execute(1).get(timeout=30)
        assert compiled.execute(2).get(timeout=30) == 2  # loop survived
    finally:
        compiled.teardown()
    ray_tpu.kill(node.resolve_actor_handle())


def _stage_fn(w, h):
    import jax.numpy as jnp

    return jnp.tanh(h @ w)


def test_mpmd_parity_bitexact_vs_pipeline_apply(pipeline_cluster):
    """Acceptance oracle: the MPMD pipeline's outputs are BIT-EXACT vs the
    single-controller pipeline_apply on identical stacked params/inputs —
    at M == S and at M > S — its steady state leaves the raylet, the
    ownership table and the node's store alone, and the per-stage loop
    stats expose the measured bubble."""
    import jax

    from ray_tpu._private import worker_context
    from ray_tpu.experimental.device_object import device_object_stats
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.mpmd_pipeline import mpmd_pipeline
    from ray_tpu.parallel.pipeline import pipeline_apply

    cw = worker_context.get_core_worker()
    n_stages, d = 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(1), (n_stages, d, d)) * 0.3
    mesh = create_mesh(MeshConfig(pp=4, dp=2))
    pipe = mpmd_pipeline(_stage_fn, ws, num_microbatches=4)
    try:
        for M in (4, 8):  # M == S and M > S
            x = jax.random.normal(jax.random.PRNGKey(M), (M * 2, d))
            ref = np.asarray(
                pipeline_apply(_stage_fn, ws, x, mesh, num_microbatches=M)
            )
            out = np.asarray(pipe.apply(x, num_microbatches=M))
            assert np.array_equal(out, ref), f"M={M}: MPMD != pipeline_apply"
        # Steady state of the schedule just warmed: an apply() sends the
        # raylet nothing and mints no ObjectRef, no activation lands in the
        # node's store, nothing resolves over the host path, and every hop
        # is a descriptor whose payload went out of band. Control-plane
        # baselines LAST: the probes before them are classic calls.
        store0 = cw.raylet.call("get_state")["store"]["num_objects"]
        stages0, driver0 = pipe.stage_devobj_stats(), device_object_stats()
        raylet_seq0, owned0 = cw.raylet._seq, len(cw.owned)
        for _ in range(3):
            pipe.apply(x, num_microbatches=8)
        assert cw.raylet._seq == raylet_seq0
        assert len(cw.owned) <= owned0
        assert cw.raylet.call("get_state")["store"]["num_objects"] == store0
        stages1 = pipe.stage_devobj_stats()
        assert [s["transfers_host"] for s in stages1] == [
            s["transfers_host"] for s in stages0
        ], (stages0, stages1)
        assert device_object_stats()["transfers_host"] == driver0["transfers_host"]
        assert all(
            s1["chan_sends"] - s0["chan_sends"] >= 3 * 8
            for s0, s1 in zip(stages0, stages1)
        ), (stages0, stages1)
        # Non-divisible batches fail loudly, like pipeline_apply.
        bad = jax.random.normal(jax.random.PRNGKey(9), (10, d))
        with pytest.raises(AssertionError, match="not divisible"):
            pipe.apply(bad, num_microbatches=4)

        pipe.reset_stage_stats()
        x = jax.random.normal(jax.random.PRNGKey(3), (16, d))
        pipe.apply(x, num_microbatches=8)
        rows = pipe.stage_stats()
        assert len(rows) == n_stages
        assert all(r["iters"] >= 8 for r in rows), rows
        assert 0.0 <= pipe.bubble_fraction() < 1.0
    finally:
        pipe.teardown()


def test_mpmd_chaos_sigkill_stage_reclaims_everything(pipeline_cluster):
    """Acceptance: SIGKILL one stage mid-schedule. The in-flight and
    subsequent microbatches surface a typed ActorDiedError naming the dead
    stage (descriptor waits abort on the poison, they don't hang out the
    grace window), and teardown reclaims the full data plane: channel
    slots back to the arena, driver payload scope freed, surviving stages'
    pinned payloads freed — counters return to baseline."""
    import jax

    from ray_tpu._private import worker_context
    from ray_tpu.experimental.device_object import device_object_stats
    from ray_tpu.parallel.mpmd_pipeline import mpmd_pipeline

    cw = worker_context.get_core_worker()
    n_stages, d = 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(1), (n_stages, d, d)) * 0.3
    driver_base = device_object_stats()["resident_count"]
    chan0 = cw.raylet.call("get_state")["store"]["num_channels"]
    pipe = mpmd_pipeline(_stage_fn, ws, num_microbatches=4)
    survivors = [s for i, s in enumerate(pipe.stages) if i != 1]
    victim_pid = ray_tpu.get(pipe.stages[1].pid.remote(), timeout=30)
    try:
        x = jax.random.normal(jax.random.PRNGKey(2), (8, d))
        assert pipe.apply(x, num_microbatches=4).shape == (8, d)

        # Mid-schedule: several microbatches in flight when stage 1 dies.
        x_mb = jax.random.normal(jax.random.PRNGKey(3), (2, d))
        refs = [pipe.compiled.execute(x_mb) for _ in range(3)]
        os.kill(victim_pid, signal.SIGKILL)
        with pytest.raises(ActorDiedError, match="run"):
            for r in refs:
                r.get(timeout=60)
            # Even if every in-flight microbatch drained before the signal
            # landed, the next iterations must surface the typed death
            # (bounded: the driver monitor plants poison within seconds).
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                pipe.compiled.execute(x_mb).get(timeout=60)
    finally:
        pipe.teardown(kill_actors=False)

    # Full reclamation: channels back to the arena, driver scope freed,
    # surviving stages' pinned payloads freed.
    assert cw.raylet.call("get_state")["store"]["num_channels"] == chan0
    st = _drain_resident(device_object_stats, target=driver_base)
    assert st["resident_count"] <= driver_base, st
    for s in survivors:
        st = _drain_resident(
            lambda s=s: ray_tpu.get(s.devobj_stats.remote(), timeout=30), target=0
        )
        assert st["resident_count"] == 0, st
    for s in survivors:
        ray_tpu.kill(s)
