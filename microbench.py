"""Scheduling/object-plane envelope microbenchmark.

Analog of `ray microbenchmark` (reference: python/ray/_private/ray_perf.py:93)
plus envelope stresses from release/benchmarks (queued-task depth, actor
count, object broadcast). Run per round; results land in MICROBENCH_r{N}.json
so the envelope is tracked across rounds (VERDICT r1 #5). Every artifact
includes a `deltas_vs_prev` block diffing against the previous round's JSON
so regressions are named in the artifact itself (VERDICT r5 #8).

Usage: python microbench.py [--round N] [--quick]
       python microbench.py --hop-budget   # per-hop dispatch latency table
       python microbench.py --smoke        # <30s CI sanity pass (tier-1)
       python microbench.py --dag          # classic vs compiled DAG dispatch
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("RAY_TPU_NUM_TPUS", "0")


def timeit(fn, duration=2.0, multiplier=1, warmup=1):
    for _ in range(warmup):
        fn()
    start = time.perf_counter()
    count = 0
    while time.perf_counter() - start < duration:
        fn()
        count += 1
    dt = time.perf_counter() - start
    return count * multiplier / dt


def basic_suite(results, duration):
    import numpy as np

    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)

    @ray_tpu.remote
    def small():
        return b"ok"

    @ray_tpu.remote
    class Actor:
        def ping(self):
            return b"ok"

    a = Actor.remote()
    ray_tpu.get(a.ping.remote())

    results["task_sync_per_s"] = round(timeit(lambda: ray_tpu.get(small.remote()), duration), 1)
    results["task_async100_per_s"] = round(
        timeit(lambda: ray_tpu.get([small.remote() for _ in range(100)]), duration, 100), 1
    )
    results["actor_call_sync_per_s"] = round(timeit(lambda: ray_tpu.get(a.ping.remote()), duration), 1)
    results["actor_call_async100_per_s"] = round(
        timeit(lambda: ray_tpu.get([a.ping.remote() for _ in range(100)]), duration, 100), 1
    )
    arr = np.zeros(1024 * 1024, dtype=np.uint8)
    results["put_1mib_per_s"] = round(timeit(lambda: ray_tpu.put(arr), duration), 1)
    results["putget_1mib_per_s"] = round(
        timeit(lambda: ray_tpu.get(ray_tpu.put(arr)), duration), 1
    )
    ray_tpu.shutdown()


def hop_budget_suite(results, duration):
    """--hop-budget: measured per-hop dispatch latency budget.

    Runs the sync ping-pong loops with RAY_TPU_HOP_TIMING=1 so every frame
    carries monotonic stage timestamps, then prints/records the per-hop µs
    table per transport path: warm lease (steady-state normal task, raylet
    OFF the path), direct actor call, and the classic raylet-queued path
    (SPREAD forces it) as the before/after contrast."""
    os.environ["RAY_TPU_HOP_TIMING"] = "1"
    try:
        import ray_tpu
        from ray_tpu.util import tracing

        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)

        @ray_tpu.remote
        def small():
            return b"ok"

        @ray_tpu.remote(scheduling_strategy="SPREAD")
        def small_spread():
            return b"ok"

        @ray_tpu.remote
        class Actor:
            def ping(self):
                return b"ok"

        a = Actor.remote()
        ray_tpu.get(a.ping.remote())
        ray_tpu.get(small.remote())
        ray_tpu.get(small_spread.remote())
        tracing.drain_hop_records()  # discard warmup records
        records = []
        for fn in (
            lambda: ray_tpu.get(small.remote()),        # warm lease
            lambda: ray_tpu.get(a.ping.remote()),       # direct actor
            lambda: ray_tpu.get(small_spread.remote()),  # classic raylet path
        ):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < duration:
                fn()
            # Harvest per phase: the owner's hop ring buffer holds 4096
            # records, and a fast later phase would evict an earlier one's.
            records.extend(tracing.drain_hop_records())
        summary = tracing.summarize_hop_records(records)
        results["hop_budget"] = summary
        print(tracing.format_hop_table(summary))
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_HOP_TIMING", None)


def dag_suite(results, duration):
    """--dag: classic dag.execute() vs compiled execution on a 4-stage actor
    pipeline (ISSUE 7 acceptance artifact, DAGBENCH_r{N}.json).

    Runs with RAY_TPU_HOP_TIMING=1 so compiled iterations leave their
    path="compiled" stage stamps, and records the control-plane evidence
    directly: the driver->raylet RPC count and the owned-ObjectRef table
    delta across the compiled loop (both must be 0 per iteration)."""
    os.environ["RAY_TPU_HOP_TIMING"] = "1"
    try:
        import ray_tpu
        from ray_tpu._private import worker_context
        from ray_tpu.dag import InputNode
        from ray_tpu.util import tracing

        ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)

        @ray_tpu.remote
        class Stage:
            def work(self, x):
                return x + 1

        with InputNode() as inp:
            dag = inp
            for _ in range(4):
                dag = Stage.bind().work.bind(dag)

        # Classic path (per-call specs/refs/RPCs; actor gang reused via the
        # per-DAG actor cache).
        assert ray_tpu.get(dag.execute(0)) == 4  # create + warm the gang
        classic_per_s = timeit(lambda: ray_tpu.get(dag.execute(0)), duration)
        results["dag_classic_per_s"] = round(classic_per_s, 1)
        results["dag_classic_latency_ms"] = round(1000.0 / classic_per_s, 3)
        tracing.drain_hop_records()

        # Compiled path: same gang, pre-allocated channels, resident loops.
        compiled = dag.experimental_compile()
        try:
            assert compiled.execute(0).get() == 4  # warm the loops
            cw = worker_context.get_core_worker()
            raylet_seq0 = cw.raylet._seq
            owned0 = len(cw.owned)
            t0 = time.perf_counter()
            n = 0
            while time.perf_counter() - t0 < duration:
                assert compiled.execute(0).get() == 4
                n += 1
            dt = time.perf_counter() - t0
            results["dag_compiled_per_s"] = round(n / dt, 1)
            results["dag_compiled_latency_ms"] = round(dt * 1000.0 / n, 3)
            results["dag_compiled_iters"] = n
            # Control-plane evidence for the acceptance claim.
            results["dag_compiled_raylet_rpcs_per_iter"] = round(
                (cw.raylet._seq - raylet_seq0) / n, 6
            )
            results["dag_compiled_new_object_refs_per_iter"] = round(
                (len(cw.owned) - owned0) / n, 6
            )
            results["dag_speedup_vs_classic"] = round(
                results["dag_compiled_per_s"] / classic_per_s, 2
            )
            summary = tracing.summarize_hop_records(tracing.drain_hop_records())
            results["dag_hop_budget"] = summary
            print(tracing.format_hop_table(summary))
        finally:
            compiled.teardown()
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_HOP_TIMING", None)


def pipeline_suite(results, quick=False):
    """--pipeline: 4-stage MPMD pipeline over compiled graphs (ISSUE 12
    acceptance artifact, PIPEBENCH_r{N}.json).

    Arms on identical stacked params / inputs (stage_fn = tanh(h @ w),
    d=16, mb=4 — small activations so control-plane cost, not byte copies,
    is what's measured; a larger-activation shape rides along for honesty):

    - ``classic``: the SAME ``tensor_transport="collective"`` stage actors
      driven by classic dispatch — chained ``.remote`` calls, descriptor
      ObjectRefs, a ``devobj_pull`` round trip per hop (the PR 9 path with
      the full per-call control plane). The apples-to-apples baseline: same
      device-object semantics, classic control plane.
    - ``classic_host``: plain actors, activations through the host object
      plane (inline/plasma) — the pre-device-plane pipeline.
    - ``mpmd``: ``parallel/mpmd_pipeline.py`` — compiled DAG, resident
      loops, descriptor slots, eager out-of-band payload streaming.
    - ``spmd``: single-controller ``pipeline_apply`` (one jitted program on
      the driver's pp mesh) — the parity oracle and the single-process
      reference point (no process boundaries: on this 1-CPU box its raw
      mb/s is NOT the MPMD comparison axis; per-stage meshes/programs are).

    Evidence recorded per the acceptance criteria: bit-exact parity of the
    MPMD outputs vs pipeline_apply, raylet RPCs per iteration (0), store
    object delta (0 — no activation touches the shm object store), stage
    host-transfer delta (0 — no host-fallback resolutions in steady state),
    and measured bubble fraction at M in {4, 16} next to the theoretical
    (S-1)/(M+S-1)."""
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import numpy as np

    import ray_tpu
    from ray_tpu._private import worker_context

    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.mpmd_pipeline import PipelineStageActor, mpmd_pipeline
    from ray_tpu.parallel.pipeline import pipeline_apply

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    n_stages, d, mb = 4, 16, 4
    duration = 1.0 if quick else 3.0
    Ms = (4,) if quick else (4, 16)
    ws = jax.random.normal(jax.random.PRNGKey(1), (n_stages, d, d)) * 0.3
    results["pipeline_shape"] = {"n_stages": n_stages, "d": d, "mb": mb}
    cw = worker_context.get_core_worker()

    def store_objects() -> int:
        return cw.raylet.call("get_state")["store"]["num_objects"]

    def batch(M):
        return jax.random.normal(jax.random.PRNGKey(2), (M * mb, d))

    # ---- spmd arm + the parity reference -------------------------------
    mesh = create_mesh(MeshConfig(pp=4, dp=2))
    x4 = batch(4)
    ref4 = np.asarray(pipeline_apply(stage_fn, ws, x4, mesh, num_microbatches=4))
    for M in Ms:
        x = batch(M)
        rate = timeit(
            lambda: np.asarray(
                pipeline_apply(stage_fn, ws, x, mesh, num_microbatches=M)
            ),
            duration / 2,
        )
        results[f"pipeline_spmd_m{M}_iter_per_s"] = round(rate, 2)
        results[f"pipeline_spmd_m{M}_mb_per_s"] = round(rate * M, 1)

    # ---- classic arm: same tensor_transport actors, classic dispatch ---
    nodes = [
        PipelineStageActor.bind(stage_fn, ws[k], k, n_stages, None)
        for k in range(n_stages)
    ]
    handles = [n.resolve_actor_handle() for n in nodes]
    ray_tpu.get([h.ready.remote() for h in handles], timeout=120)
    ray_tpu.get([h.warmup.remote(jnp.zeros((mb, d))) for h in handles], timeout=120)

    def classic_apply(handles_, x_mbs):
        refs = []
        for m in range(len(x_mbs)):
            r = x_mbs[m]
            for h in handles_:
                r = h.run.remote(r)
            refs.append(r)
        return ray_tpu.get(refs, timeout=120)

    for M in Ms:
        x_mbs = batch(M).reshape(M, mb, d)
        rate = timeit(lambda: classic_apply(handles, x_mbs), duration)
        results[f"pipeline_classic_m{M}_iter_per_s"] = round(rate, 2)
        results[f"pipeline_classic_m{M}_mb_per_s"] = round(rate * M, 1)
    for h in handles:
        ray_tpu.kill(h)

    # ---- classic_host arm: plain actors, host object plane -------------
    @ray_tpu.remote
    class HostStage:
        def __init__(self, fn, params):
            import jax as _jax

            self._fn = _jax.jit(fn)
            self.params = _jax.device_put(params)

        def run(self, h):
            return self._fn(self.params, h)

    host_handles = [HostStage.remote(stage_fn, ws[k]) for k in range(n_stages)]
    classic_apply(host_handles, batch(4).reshape(4, mb, d))  # warm
    for M in Ms:
        x_mbs = batch(M).reshape(M, mb, d)
        rate = timeit(lambda: classic_apply(host_handles, x_mbs), duration)
        results[f"pipeline_classic_host_m{M}_iter_per_s"] = round(rate, 2)
        results[f"pipeline_classic_host_m{M}_mb_per_s"] = round(rate * M, 1)
    for h in host_handles:
        ray_tpu.kill(h)

    # ---- mpmd arm ------------------------------------------------------
    from ray_tpu.experimental.device_object import device_object_stats

    pipe = mpmd_pipeline(
        stage_fn, ws, num_microbatches=4, warmup_x=jnp.zeros((mb, d))
    )
    # Parity oracle: bit-exact vs pipeline_apply on identical params/input.
    out4 = np.asarray(pipe.apply(x4, num_microbatches=4))
    results["pipeline_parity_bitexact"] = bool(np.array_equal(out4, ref4))
    assert results["pipeline_parity_bitexact"], "MPMD output != pipeline_apply"

    for M in Ms:
        x = batch(M)
        pipe.apply(x, num_microbatches=M)  # warm this schedule
        pipe.reset_stage_stats()
        store0 = store_objects()
        stage_stats0 = pipe.stage_devobj_stats()
        driver0 = device_object_stats()
        # Control-plane baselines LAST: the probes above are classic calls
        # (a raylet get_state RPC, ObjectRef-bearing actor calls) and must
        # not count against the measured window.
        raylet_seq0 = cw.raylet._seq
        owned0 = len(cw.owned)
        t0 = time.perf_counter()
        iters = 0
        while time.perf_counter() - t0 < duration:
            pipe.apply(x, num_microbatches=M)
            iters += 1
        dt = time.perf_counter() - t0
        results[f"pipeline_mpmd_m{M}_iter_per_s"] = round(iters / dt, 2)
        results[f"pipeline_mpmd_m{M}_mb_per_s"] = round(iters * M / dt, 1)
        results[f"pipeline_mpmd_m{M}_bubble_measured"] = round(
            pipe.bubble_fraction(), 4
        )
        results[f"pipeline_mpmd_m{M}_bubble_theoretical"] = round(
            (n_stages - 1) / (M + n_stages - 1), 4
        )
        # Control-plane + zero-host-copy evidence (deterministic counters).
        results[f"pipeline_mpmd_m{M}_raylet_rpcs_per_iter"] = round(
            (cw.raylet._seq - raylet_seq0) / iters, 6
        )
        results[f"pipeline_mpmd_m{M}_new_object_refs_per_iter"] = round(
            (len(cw.owned) - owned0) / iters, 6
        )
        results[f"pipeline_mpmd_m{M}_store_objects_delta"] = (
            store_objects() - store0
        )
        stage_stats1 = pipe.stage_devobj_stats()
        results[f"pipeline_mpmd_m{M}_host_transfers_delta"] = sum(
            s1["transfers_host"] - s0["transfers_host"]
            for s0, s1 in zip(stage_stats0, stage_stats1)
        ) + (device_object_stats()["transfers_host"] - driver0["transfers_host"])
        results[f"pipeline_mpmd_m{M}_chan_sends"] = sum(
            s1["chan_sends"] - s0["chan_sends"]
            for s0, s1 in zip(stage_stats0, stage_stats1)
        )
    results["pipeline_speedup_vs_classic"] = round(
        results["pipeline_mpmd_m4_iter_per_s"]
        / results["pipeline_classic_m4_iter_per_s"],
        2,
    )
    results["pipeline_speedup_vs_classic_host"] = round(
        results["pipeline_mpmd_m4_iter_per_s"]
        / results["pipeline_classic_host_m4_iter_per_s"],
        2,
    )
    # Larger-activation shape for honesty (256 KiB activations: byte copies
    # start to dominate both arms and compute equalizes them; the control-
    # plane win above is the claim, this row bounds it).
    if not quick:
        d2, mb2 = 512, 128
        ws2 = jax.random.normal(jax.random.PRNGKey(4), (n_stages, d2, d2)) * 0.05
        pipe2 = mpmd_pipeline(
            stage_fn, ws2, num_microbatches=4,
            warmup_x=jnp.zeros((mb2, d2)),
        )
        x2 = jax.random.normal(jax.random.PRNGKey(5), (4 * mb2, d2))
        pipe2.apply(x2, num_microbatches=4)
        rate = timeit(lambda: pipe2.apply(x2, num_microbatches=4), duration / 2)
        results["pipeline_mpmd_256kib_m4_iter_per_s"] = round(rate, 2)
        pipe2.teardown()
    pipe.teardown()
    ray_tpu.shutdown()


def device_objects_suite(results, duration):
    """--device-objects: device-ref handoff vs host-shm put/get (ISSUE 9
    acceptance artifact, DEVBENCH_r{N}.json).

    Same-process: ``put(arr, tensor_transport="collective")`` seals only a
    ~300-byte descriptor and ``get`` hands back the LIVE array — the
    before/after contrast is the host path's serialize→shm→deserialize
    round trip at 1 MiB / 32 MiB. Control-plane evidence rides along: the
    node store's object count across the device loop (must be 0 — zero shm
    copies of the payload) and the plane's own transfer counters.
    Actor→actor: a tensor_transport holder hands a 1 MiB ref to a consumer
    actor over a shared cpu collective group (on this CPU testbed the p2p
    mailbox rides the GCS KV — a correctness stand-in for the ICI path, so
    absolute throughput is NOT the device-plane claim; zero host-shm
    payload traffic is)."""
    import ray_tpu
    from ray_tpu._private import worker_context
    from ray_tpu.experimental.device_object import device_object_stats

    ray_tpu.init(num_cpus=4, object_store_memory=512 * 1024 * 1024)
    import jax.numpy as jnp

    cw = worker_context.get_core_worker()

    def store_objects() -> int:
        return cw.raylet.call("get_state")["store"]["num_objects"]

    for mib in (1, 32):
        arr = jnp.zeros(mib * 1024 * 1024 // 4, jnp.float32)
        arr.block_until_ready()
        results[f"host_putget_{mib}mib_per_s"] = round(
            timeit(lambda: ray_tpu.get(ray_tpu.put(arr)), duration), 1
        )

        def dev_roundtrip():
            out = ray_tpu.get(ray_tpu.put(arr, tensor_transport="collective"))
            assert out is arr  # live array, zero payload copies

        before = store_objects()
        t0 = device_object_stats()
        results[f"devobj_putget_{mib}mib_per_s"] = round(timeit(dev_roundtrip, duration), 1)
        t1 = device_object_stats()
        results[f"devobj_putget_{mib}mib_store_objects_delta"] = store_objects() - before
        results[f"devobj_putget_{mib}mib_local_transfers"] = (
            t1["transfers_local"] - t0["transfers_local"]
        )

    # Actor→actor 1 MiB handoff: host-shm path vs device plane + collective.
    @ray_tpu.remote
    class HostHolder:
        def make(self):
            import jax.numpy as jnp

            return jnp.zeros(1024 * 1024 // 4, jnp.float32)

    @ray_tpu.remote(tensor_transport="collective")
    class DevHolder:
        def make(self):
            import jax.numpy as jnp

            return jnp.zeros(1024 * 1024 // 4, jnp.float32)

        def init_collective(self, world_size, rank, backend, group_name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)

    @ray_tpu.remote
    class Consumer:
        def init_collective(self, world_size, rank, backend, group_name):
            from ray_tpu.util import collective as col

            col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)

        def consume(self, w):
            return float(w[0])

    from ray_tpu.util import collective as col

    host_holder, dev_holder, consumer = HostHolder.remote(), DevHolder.remote(), Consumer.remote()
    col.create_collective_group(
        [dev_holder, consumer], backend="cpu", group_name="devbench"
    )
    results["handoff_host_1mib_per_s"] = round(
        timeit(
            lambda: ray_tpu.get(consumer.consume.remote(host_holder.make.remote())),
            duration,
        ),
        1,
    )
    results["handoff_devobj_1mib_per_s"] = round(
        timeit(
            lambda: ray_tpu.get(consumer.consume.remote(dev_holder.make.remote())),
            duration,
        ),
        1,
    )
    ray_tpu.shutdown()


def collective_suite(results, quick=False, arms=("tree", "flat")):
    """--collective: ISSUE 15 — learner→fleet weight-sync fan-out A/B, plus
    ISSUE 16 — relay-tree vs flat group broadcast and the tree allreduce
    oracle (COLLBENCH_r{N}.json).

    A tensor_transport learner actor holds a payload_mib flat weight vector
    device-resident; K sampler actors apply it each sync. Baseline arm =
    the K-serial-unicast path every pre-15 sync paid (each sampler's
    resolve does its own devobj_pull → holder serializes PER SAMPLER and
    ships through the group's GCS-KV mailbox). Broadcast arm = ONE
    device_object.broadcast(ref, group): one serialize, concurrent acked
    chunk pushes at every sampler's direct mailbox, samplers resolve from
    their inbox with zero pull round trips. Both arms end in the same
    state (every sampler applied the weights), timed over the same actors
    in the same cluster; the device path's zero-host-store evidence
    (store_objects_delta) rides along. An end-to-end Podracer row (IMPALA
    on CartPole, device_broadcast vs host weight sync) closes the loop."""
    import ray_tpu
    from ray_tpu._private import worker_context
    from ray_tpu.experimental import device_object
    from ray_tpu.util import collective as col

    fleet = [2] if quick else [2, 4, 8]
    # 8 MiB ≈ a 2M-param f32 model: big enough that the payload path (the
    # thing this issue changes) dominates the K fixed-cost actor round
    # trips both arms share.
    payload_mib = 2 if quick else 8
    reps = 2 if quick else 5
    n = payload_mib * 1024 * 1024 // 4
    ray_tpu.init(num_cpus=16, object_store_memory=512 * 1024 * 1024)
    cw = worker_context.get_core_worker()

    def store_objects() -> int:
        return cw.raylet.call("get_state")["store"]["num_objects"]

    @ray_tpu.remote(tensor_transport="collective")
    class LearnerActor:
        def __init__(self):
            self._version = 0

        def init_collective(self, world_size, rank, backend, group_name):
            col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)

        def make_weights(self, n):
            import jax.numpy as jnp

            self._version += 1
            return jnp.full((n,), float(self._version), jnp.float32)

        def residents(self):
            from ray_tpu.experimental.device_object import device_object_stats

            return device_object_stats()["resident_count"]

    @ray_tpu.remote
    class SamplerActor:
        def init_collective(self, world_size, rank, backend, group_name):
            col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)

        def apply(self, w):
            # Arg resolution already resolved the descriptor (inbox on the
            # broadcast arm, devobj_pull unicast on the baseline arm).
            return float(w[0])

    results["collective_payload_mib"] = payload_mib
    for K in fleet:
        learner = LearnerActor.remote()
        samplers = [SamplerActor.remote() for _ in range(K)]
        group = f"wsync{K}"
        col.create_collective_group([learner] + samplers, backend="cpu", group_name=group)

        def sync_serial():
            ref = learner.make_weights.remote(n)
            t0 = time.perf_counter()
            for s in samplers:
                ray_tpu.get(s.apply.remote(ref), timeout=120)
            return time.perf_counter() - t0

        def sync_broadcast():
            ref = learner.make_weights.remote(n)
            t0 = time.perf_counter()
            info = device_object.broadcast(ref, group, timeout=120)
            assert len(info["ok_ranks"]) == K, info
            for s in samplers:
                ray_tpu.get(s.apply.remote(ref), timeout=120)
            return time.perf_counter() - t0

        sync_serial()  # warm both code paths + worker jax imports
        sync_broadcast()
        serial = sorted(sync_serial() for _ in range(reps))[reps // 2]
        # Snapshot AFTER the serial arm so the delta certifies the
        # broadcast arm alone.
        before = store_objects()
        bcast = sorted(sync_broadcast() for _ in range(reps))[reps // 2]
        results[f"wsync_serial_k{K}_s"] = round(serial, 4)
        results[f"wsync_broadcast_k{K}_s"] = round(bcast, 4)
        results[f"wsync_serial_k{K}_mib_per_s"] = round(K * payload_mib / serial, 1)
        results[f"wsync_broadcast_k{K}_mib_per_s"] = round(K * payload_mib / bcast, 1)
        results[f"wsync_speedup_k{K}"] = round(serial / bcast, 2)
        results[f"wsync_broadcast_k{K}_store_objects_delta"] = store_objects() - before
        # Ownership protocol: per-sync weight refs were dropped, so the
        # learner's residents must drain back to zero (bounded wait for the
        # async devobj_free pushes).
        deadline = time.monotonic() + 30
        residents = ray_tpu.get(learner.residents.remote())
        while residents > 0 and time.monotonic() < deadline:
            time.sleep(0.2)
            residents = ray_tpu.get(learner.residents.remote())
        results[f"wsync_k{K}_residents_after"] = residents
        for a in [learner] + samplers:
            ray_tpu.kill(a)

    # ---- ISSUE 16: relay-tree vs flat broadcast + tree allreduce oracle ----
    # On this 1-core loopback box raw wire time cannot separate the
    # topologies, so the A/B runs under the PR 10 modeled-link convention:
    # a 64 MiB/s per-process egress gate (p2p.set_modeled_egress) charges
    # every collective push its wire time — the flat root pays K payloads
    # through its gate, the tree root only its log-K children (relays pay
    # theirs in PARALLEL on other processes). Raw loopback rows ride along
    # unmodeled for honesty.
    from ray_tpu.util.collective.p2p import COLL, set_modeled_egress

    MODELED_MIB_S = 64.0
    relay_mib = 1 if quick else 4
    n_relay = relay_mib * 1024 * 1024 // 4
    relay_fleet = [3] if quick else [4, 8]
    relay_reps = 2 if quick else 3

    @ray_tpu.remote
    class RelayMember:
        def init_collective(self, world_size, rank, backend, group_name):
            col.init_collective_group(world_size, rank, backend=backend, group_name=group_name)

        def set_egress(self, mib_per_s):
            from ray_tpu.util.collective.p2p import set_modeled_egress as sme

            sme(mib_per_s)
            return True

        def drain(self, group_name, src_rank, tag):
            import numpy as np

            out = col.get_group(group_name).bcast_recv_payload(src_rank, tag, timeout=120)
            return int(np.asarray(out).size)

        def allreduce(self, group_name, tag, n, flat_ring=False):
            import numpy as np

            g = col.get_group(group_name)
            v = ((np.arange(n) % 97) + 3.0 * g.rank).astype(np.float32)
            out = g.allreduce(v) if flat_ring else g.allreduce_payload(v, tag)
            return np.asarray(out)

        def reducescatter(self, group_name, tag, k, n, flat_ring=False):
            import numpy as np

            g = col.get_group(group_name)
            v = ((np.arange(k * n).reshape(k, n) % 97) + 3.0 * g.rank).astype(
                np.float32
            )
            out = g.reducescatter(v) if flat_ring else g.reducescatter_payload(v, tag)
            return np.asarray(out)

        def coll_stats(self):
            from ray_tpu.util.collective.p2p import COLL as C

            return {k: getattr(C, k) for k in C.__slots__}

    import numpy as np

    results["relay_payload_mib"] = relay_mib
    results["relay_modeled_egress_mib_per_s"] = MODELED_MIB_S
    for K in relay_fleet:
        members = [RelayMember.remote() for _ in range(K)]
        group = f"relay{K}"
        col.init_collective_group(K + 1, 0, backend="cpu", group_name=group)
        ray_tpu.get(
            [m.init_collective.remote(K + 1, i + 1, "cpu", group) for i, m in enumerate(members)],
            timeout=120,
        )
        g = col.get_group(group)
        payload = np.arange(n_relay, dtype=np.float32)
        seq = iter(range(10_000))

        def timed_bcast(topology):
            tag = f"b{next(seq)}"
            t0 = time.perf_counter()
            info = g.bcast_send_payload(
                payload, tag, timeout=120, mailbox_fallback=False, topology=topology
            )
            dt = time.perf_counter() - t0
            assert len(info["ok_ranks"]) == K and not info["failed"], info
            # Drain member inboxes OUTSIDE the timed send-to-ack window.
            ray_tpu.get([m.drain.remote(group, 0, tag) for m in members], timeout=120)
            return dt, info

        def set_gate(mib):
            set_modeled_egress(mib)
            ray_tpu.get([m.set_egress.remote(mib) for m in members], timeout=60)

        store_before = store_objects()
        forwards_before = sum(
            s["relay_forwards"]
            for s in ray_tpu.get([m.coll_stats.remote() for m in members], timeout=60)
        )
        for topology in arms:
            raw_dt, info = timed_bcast(topology)  # warm + raw loopback row
            results[f"relay_{topology}_k{K}_raw_s"] = round(raw_dt, 4)
            if topology == "tree":
                assert info["topology"] == "tree", info
                results[f"relay_tree_k{K}_root_egress_frac"] = round(
                    info["root_egress_bytes"] / (K * info["bytes"]), 3
                )
            set_gate(MODELED_MIB_S)
            try:
                dts = sorted(timed_bcast(topology)[0] for _ in range(relay_reps))
            finally:
                set_gate(None)
            dt = dts[relay_reps // 2]
            results[f"relay_{topology}_k{K}_s"] = round(dt, 4)
            results[f"relay_{topology}_k{K}_agg_mib_per_s"] = round(K * relay_mib / dt, 1)
        if "tree" in arms and "flat" in arms:
            results[f"relay_tree_speedup_k{K}"] = round(
                results[f"relay_flat_k{K}_s"] / results[f"relay_tree_k{K}_s"], 2
            )
        forwards_after = sum(
            s["relay_forwards"]
            for s in ray_tpu.get([m.coll_stats.remote() for m in members], timeout=60)
        )
        results[f"relay_k{K}_relay_forwards"] = forwards_after - forwards_before
        results[f"relay_k{K}_store_objects_delta"] = store_objects() - store_before
        if "tree" in arms:
            # Mid-tree relays actually carried payload, and nothing touched
            # the host store — the quick-smoke contract.
            assert results[f"relay_k{K}_relay_forwards"] > 0, results
        assert results[f"relay_k{K}_store_objects_delta"] == 0, results

        # Allreduce arm (raw loopback, both transports ungated): tree
        # reduce-up/broadcast-down vs the flat GCS ring, with a BIT-EXACT
        # integer-float32 oracle — combine order must not change the sum.
        ar_group = f"ar{K}"
        ray_tpu.get(
            [m.init_collective.remote(K, i, "cpu", ar_group) for i, m in enumerate(members)],
            timeout=120,
        )
        n_ar = (1 if quick else 2) * 1024 * 1024 // 4
        expected = np.sum(
            [((np.arange(n_ar) % 97) + 3.0 * r).astype(np.float32) for r in range(K)],
            axis=0,
            dtype=np.float64,
        ).astype(np.float32)
        for label, flat_ring in (("tree", False), ("ring", True)):
            t0 = time.perf_counter()
            outs = ray_tpu.get(
                [m.allreduce.remote(ar_group, f"ar-{label}", n_ar, flat_ring) for m in members],
                timeout=240,
            )
            dt = time.perf_counter() - t0
            for out in outs:
                assert (out == expected).all(), f"allreduce {label} k{K}: oracle mismatch"
            results[f"allreduce_{label}_k{K}_s"] = round(dt, 4)
            results[f"allreduce_{label}_k{K}_agg_mib_per_s"] = round(
                K * (n_ar * 4 / 2**20) / dt, 1
            )
        results[f"allreduce_k{K}_bit_exact"] = 1

        # Reducescatter verb (ISSUE 20 satellite): tree reduce-to-root +
        # direct-mailbox shard scatter vs the flat GCS-mailbox ring, with
        # the same integer-float32 bit-exact oracle — every rank's shard
        # must equal its row of the full reduction regardless of combine
        # order or which transport carried it.
        n_rs = (256 if quick else 512) * 1024 // 4
        full_rs = np.sum(
            [
                ((np.arange(K * n_rs).reshape(K, n_rs) % 97) + 3.0 * r).astype(
                    np.float32
                )
                for r in range(K)
            ],
            axis=0,
            dtype=np.float64,
        ).astype(np.float32)
        scatter0 = sum(
            s["scatter_bytes"]
            for s in ray_tpu.get([m.coll_stats.remote() for m in members], timeout=60)
        )
        for label, flat_ring in (("tree", False), ("ring", True)):
            t0 = time.perf_counter()
            outs = ray_tpu.get(
                [
                    m.reducescatter.remote(ar_group, f"rs-{label}", K, n_rs, flat_ring)
                    for m in members
                ],
                timeout=240,
            )
            dt = time.perf_counter() - t0
            # Roster position == rank here (members hold ranks 0..K-1), so
            # rank i's shard is row i of the full reduction.
            for pos, out in enumerate(outs):
                assert (np.asarray(out) == full_rs[pos]).all(), (
                    f"reducescatter {label} k{K} rank {pos}: oracle mismatch"
                )
            results[f"reducescatter_{label}_k{K}_s"] = round(dt, 4)
            results[f"reducescatter_{label}_k{K}_agg_mib_per_s"] = round(
                K * (K * n_rs * 4 / 2**20) / dt, 1
            )
        results[f"reducescatter_k{K}_bit_exact"] = 1
        results[f"reducescatter_k{K}_scatter_bytes"] = (
            sum(
                s["scatter_bytes"]
                for s in ray_tpu.get(
                    [m.coll_stats.remote() for m in members], timeout=60
                )
            )
            - scatter0
        )
        # The tree arm actually shipped shards over direct mailboxes (the
        # ring arm rides the GCS mailbox and must not touch this counter).
        assert results[f"reducescatter_k{K}_scatter_bytes"] > 0, results

        col.destroy_collective_group(group)
        col.destroy_collective_group(ar_group)
        for m in members:
            ray_tpu.kill(m)
    set_modeled_egress(None)
    ray_tpu.shutdown()

    # ---- end-to-end Podracer row: IMPALA on CartPole, host vs device sync ----
    import jax

    jax.config.update("jax_platforms", "cpu")
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig

    iters = 2 if quick else 4
    for label, overrides in (
        ("host", {"weight_sync": "host"}),
        ("device_broadcast", {"weight_sync": "device_broadcast", "learner_mesh": True}),
    ):
        ray_tpu.init(num_cpus=6)
        cfg = (
            IMPALAConfig()
            .environment("CartPole-v1")
            .rollouts(num_rollout_workers=2, rollout_fragment_length=32)
            .training(lr=5e-4, train_batch_size=128, **overrides)
            .debugging(seed=0)
        )
        algo = cfg.build()
        try:
            # Warm compile + worker spawn outside the window. TWO steps: the
            # mesh arm pays a second jit (committed-param avals) on step 2.
            algo.step()
            algo.step()
            from ray_tpu.util.collective.p2p import COLL

            bcasts0 = COLL.bcast_sends
            t0 = time.perf_counter()
            for _ in range(iters):
                algo.step()
            dt = time.perf_counter() - t0
            results[f"podracer_{label}_iters_per_s"] = round(iters / dt, 2)
            if label == "device_broadcast":
                # Every measured iteration's weight sync must actually have
                # ridden the group-broadcast plane (driver = holder here).
                results["podracer_device_broadcasts"] = COLL.bcast_sends - bcasts0
        finally:
            algo.cleanup()
        ray_tpu.shutdown()


def resize_suite(results, quick=False):
    """--collective --resize: elastic Podracer fleet (ISSUE 17) — IMPALA on
    the device-broadcast plane driven through a scripted grow/shrink
    schedule (8→16→8 samplers; 2→4→2 under --quick). Growing gang-joins
    the new samplers into the weight group at fresh tail ranks, shrinking
    evicts the tail from the roster — no group teardown either way. Per
    phase the suite records how weight syncs actually travelled: inbox
    resolves summed over the live fleet (broadcast plane) vs host-sync
    pull fallbacks, plus iterations/s and the resize wall itself. The
    elastic contract is asserted inline: after the FIRST post-resize
    iteration the fleet-wide fallback counter is FLAT and every measured
    sync rode the plane."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import ray_tpu
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig

    base = 2 if quick else 8
    peak = 4 if quick else 16
    iters = 2 if quick else 3
    schedule = [base, peak, base]
    results["resize_schedule"] = schedule
    ray_tpu.init(num_cpus=(6 if quick else peak + 2))
    cfg = (
        IMPALAConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=base,
                  rollout_fragment_length=16 if quick else 32)
        .training(lr=5e-4, train_batch_size=64 if quick else 128,
                  weight_sync="device_broadcast")
        .debugging(seed=0)
    )
    algo = cfg.build()
    try:
        assert algo._device_sync_ready, "device weight-sync group failed to form"
        algo.step()  # warm compile + worker spawn outside every window

        def fleet_totals():
            stats = [s for s in algo.workers.coll_stats() if s]
            return (
                sum(s["bcast_recvs"] for s in stats),
                sum(s["host_sync_fallbacks"] for s in stats),
            )

        for phase, n in enumerate(schedule):
            if algo.workers.num_workers != n:
                t0 = time.perf_counter()
                algo.resize_workers(n)
                results[f"resize_p{phase}_to{n}_s"] = round(time.perf_counter() - t0, 3)
            algo.step()  # the ONE iteration allowed to pull (post-resize)
            b0, f0 = fleet_totals()
            t0 = time.perf_counter()
            for _ in range(iters):
                algo.step()
            dt = time.perf_counter() - t0
            b1, f1 = fleet_totals()
            results[f"resize_p{phase}_n{n}_iters_per_s"] = round(iters / dt, 2)
            results[f"resize_p{phase}_n{n}_plane_syncs"] = b1 - b0
            results[f"resize_p{phase}_n{n}_host_fallbacks"] = f1 - f0
            # n workers x iters inbox resolves, zero pulls after the first
            # post-resize iteration — the fast-path oracle.
            assert b1 - b0 >= n * iters, results
            assert f1 - f0 == 0, results
        roster = algo.learner_group.weight_group_roster(algo._weight_group)
        results["resize_final_roster_ranks"] = roster["ranks"] if roster else None
    finally:
        algo.cleanup()
    ray_tpu.shutdown()


def recorder_overhead_suite(results, block_tasks=256, pairs=150):
    """--recorder-overhead: cost of the always-on observability plane
    (flight recorder + 1-in-64 sampled hop stamps) on the task_sync hot
    path, measured as many fine-grained paired A/B blocks.

    Noise design for a loaded 1-core box (single-block rates here swing
    +-6% while the instrumentation itself costs ~5us on a ~600us path):
    BOTH arms run inside ONE cluster against the SAME warm-leased worker,
    toggled at runtime (flight_recorder.set_enabled in driver AND worker +
    cfg.hop_sample_n in the driver, which controls the worker's stamping
    via spec.hop_ts). Blocks are COUNT-based (256 tasks ~ 150ms) and
    alternate ABBA so drift cancels within each pair; the headline
    overhead is the MEDIAN of per-pair ratios over many pairs — the only
    estimator that converged on this box (the interquartile mean rides
    along as recorder_overhead_iqmean_pct)."""
    import statistics

    import ray_tpu
    from ray_tpu._private import flight_recorder
    from ray_tpu._private.config import get_config

    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024 * 1024)

    @ray_tpu.remote
    def small():
        return b"ok"

    @ray_tpu.remote
    def _toggle(on):
        # Runs on the same warm-leased worker the loop uses (num_cpus=1 and
        # an identical shape key): flips the worker-side recorder.
        from ray_tpu._private import flight_recorder as fr

        fr.set_enabled(on)
        return True

    def set_mode(on: bool):
        flight_recorder.set_enabled(on)
        get_config().hop_sample_n = 64 if on else 0
        assert ray_tpu.get(_toggle.remote(on))

    def block(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(small.remote())
        return n / (time.perf_counter() - t0)

    # Warm the lease + both code paths.
    set_mode(True)
    block(200)
    set_mode(False)
    block(200)

    ratios = []
    on_rates, off_rates = [], []
    for i in range(pairs):
        # ABBA: alternate which arm goes first so drift cancels per pair.
        order = [True, False] if i % 2 == 0 else [False, True]
        rates = {}
        for on in order:
            set_mode(on)
            rates[on] = block(block_tasks)
        on_rates.append(rates[True])
        off_rates.append(rates[False])
        ratios.append(rates[False] / rates[True])
    set_mode(True)  # leave the plane on, as in production
    ray_tpu.shutdown()
    ratios.sort()
    q = max(1, len(ratios) // 4)
    core = ratios[q : len(ratios) - q] or ratios
    results["recorder_on_task_sync_per_s"] = round(statistics.median(on_rates), 1)
    results["recorder_off_task_sync_per_s"] = round(statistics.median(off_rates), 1)
    results["recorder_overhead_pct"] = round(
        (statistics.median(ratios) - 1.0) * 100.0, 2
    )
    results["recorder_overhead_iqmean_pct"] = round(
        (sum(core) / len(core) - 1.0) * 100.0, 2
    )
    results["recorder_pair_ratios"] = [round(r, 4) for r in ratios]
    results["recorder_pairs"] = pairs
    results["recorder_block_tasks"] = block_tasks
    print(
        f"recorder overhead on task_sync: {results['recorder_overhead_pct']}% "
        f"(on={results['recorder_on_task_sync_per_s']}/s, "
        f"off={results['recorder_off_task_sync_per_s']}/s, "
        f"median of {pairs} ABBA pair ratios; "
        f"IQ-mean={results['recorder_overhead_iqmean_pct']}%)"
    )


def chaos_suite(results, quick=False):
    """--chaos: recovery-time budget table for the wire chaos plane
    (CHAOSBENCH_r{N}.json) — pull source failover under mid-frame reset,
    device-object handoff under a lost pull round trip, broadcast
    completion under a relay partition, acall heal-after-partition — plus
    the injection-DISABLED overhead check on task_sync (PR 8's paired-ABBA
    methodology: an installed-but-inert plan vs no plan; the no-plan arm
    is the production configuration, whose entire seam cost is one is-None
    check per frame, so the inert-plan arm upper-bounds it)."""
    import statistics
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.rpc import EventLoopThread, RpcClient, RpcServer
    from ray_tpu.cluster_utils import Cluster

    def oid_for(tag):
        return tag.encode().hex().ljust(56, "0")[:56]

    mib = 4 if quick else 16
    results["chaos_object_mib"] = mib

    # ---- acall heal-after-partition (no cluster needed) ----
    srv = RpcServer("chaosbench")

    async def _pong(req):
        return {"ok": True}

    srv.register("pong", _pong)
    addr = srv.start()
    cli = RpcClient(addr, label="chaosbench-cli")
    cli.call("pong", {}, timeout=5)
    key = f"{addr[0]}:{addr[1]}"
    partition_s = 1.0
    chaos.partition("*", key)
    healed_at = {}

    def _heal():
        chaos.heal("*", key)
        healed_at["t"] = time.perf_counter()

    timer = threading.Timer(partition_s, _heal)
    timer.start()
    t0 = time.perf_counter()
    cli.call("pong", {}, timeout=5, retries=10)
    t_done = time.perf_counter()
    timer.join()
    chaos.clear()
    cli.close()
    srv.stop()
    results["acall_partition_window_s"] = partition_s
    results["acall_heal_total_s"] = round(t_done - t0, 3)
    # Time from heal to success = the backoff schedule's probe latency;
    # bounded by rpc_retry_backoff_max_ms by construction.
    results["acall_heal_probe_latency_s"] = round(t_done - healed_at["t"], 3)

    cluster = Cluster()
    try:
        nodes = [
            cluster.add_node(num_cpus=1, object_store_memory=(mib * 8 + 64) * 1024 * 1024)
            for _ in range(4)
        ]
        cluster.connect()
        cluster.wait_for_nodes()
        io = EventLoopThread.get()
        data = np.random.default_rng(13).integers(
            0, 255, mib * 1024 * 1024, dtype=np.uint8
        ).tobytes()

        def seal(node, o):
            offset = io.run(node.store.create(o, len(data)))
            node.arena.write(offset, data)
            node.store.seal(o)
            io.run(node.gcs.acall(
                "add_object_location", {"object_id": o, "node_id": node.node_id}
            ))

        def read_ok(node, o):
            offset, size = io.run(node.store.get(o))
            try:
                return bytes(node.arena.read(offset, size)) == data
            finally:
                node.store.release(o)

        # ---- pull source failover under mid-frame reset ----
        o1 = oid_for("chaosbenchA")
        seal(nodes[0], o1)
        io.run(nodes[1].pull_manager.pull(o1, 120), timeout=120)  # replica 2
        t0 = time.perf_counter()
        io.run(nodes[2].pull_manager.pull(o1, 120), timeout=120)
        results["pull_unfaulted_s"] = round(time.perf_counter() - t0, 3)
        o2 = oid_for("chaosbenchB")
        seal(nodes[0], o2)
        io.run(nodes[1].pull_manager.pull(o2, 120), timeout=120)
        chaos.install({"rules": [{
            "kind": "reset", "method": ["fetch_object_chunk"],
            "peer": f"peer-{nodes[0].node_id[:8]}", "reset_at": 9, "times": 2,
        }]}, seed=13)
        t0 = time.perf_counter()
        io.run(nodes[3].pull_manager.pull(o2, 120), timeout=120)
        results["pull_failover_reset_s"] = round(time.perf_counter() - t0, 3)
        results["pull_failover_injected"] = chaos.CHAOS_STATS.resets
        chaos.clear()
        assert read_ok(nodes[2], o1) and read_ok(nodes[3], o2)

        # ---- broadcast completion under relay partition ----
        o3 = oid_for("chaosbenchC")
        seal(nodes[0], o3)
        targets = [
            {"node_id": n.node_id, "address": list(n.address)} for n in nodes[1:]
        ]
        t0 = time.perf_counter()
        resp = io.run(
            nodes[0].rpc_broadcast_object(
                {"object_id": o3, "targets": targets, "timeout": 120.0}
            ),
            timeout=120,
        )
        results["broadcast_unfaulted_s"] = round(time.perf_counter() - t0, 3)
        assert resp["ok"], resp
        for n in nodes:
            n.store.delete(o3)
            io.run(n.gcs.acall("remove_object_location",
                               {"object_id": o3, "node_id": n.node_id}))
        o4 = oid_for("chaosbenchD")
        seal(nodes[0], o4)
        # Partition the FIRST relay child (binomial split hands it the
        # subtree) for 1s mid-broadcast, healed by timer.
        victim = nodes[1]
        cluster.partition_node(victim)
        timer = threading.Timer(1.0, lambda: cluster.heal_node(victim))
        timer.start()
        t0 = time.perf_counter()
        resp = io.run(
            nodes[0].rpc_broadcast_object(
                {"object_id": o4, "targets": targets, "timeout": 120.0}
            ),
            timeout=120,
        )
        dt = time.perf_counter() - t0
        timer.join()
        cluster.heal_node(victim)
        results["broadcast_relay_partition_s"] = round(dt, 3)
        results["broadcast_relay_partition_window_s"] = 1.0
        # Completion contract: delivered everywhere, or failures NAME nodes
        # (the push plane fails fast on an unroutable relay rather than
        # waiting out the tear — the caller owns the retry policy).
        results["broadcast_relay_partition_ok"] = bool(resp.get("ok"))
        results["broadcast_relay_partition_failed_named"] = resp.get("failed", [])
        if not resp.get("ok"):
            # The documented recovery: re-broadcast after heal completes
            # (delivered targets answer "already"; the named failures get
            # their copy now).
            t0 = time.perf_counter()
            resp2 = io.run(
                nodes[0].rpc_broadcast_object(
                    {"object_id": o4, "targets": targets, "timeout": 120.0}
                ),
                timeout=120,
            )
            results["broadcast_retry_after_heal_s"] = round(time.perf_counter() - t0, 3)
            results["broadcast_retry_after_heal_ok"] = bool(resp2.get("ok"))

        # ---- device-object handoff under a lost pull round trip ----
        import jax.numpy as jnp

        @ray_tpu.remote(max_retries=2)
        def consume(arr):
            return float(np.asarray(arr).sum())

        warm = ray_tpu.put(jnp.ones(1024, jnp.float32), tensor_transport="collective")
        assert ray_tpu.get(consume.remote(warm), timeout=120) == 1024.0
        del warm
        r1 = ray_tpu.put(jnp.ones(4096, jnp.float32), tensor_transport="collective")
        t0 = time.perf_counter()
        assert ray_tpu.get(consume.remote(r1), timeout=120) == 4096.0
        results["devobj_handoff_unfaulted_s"] = round(time.perf_counter() - t0, 3)
        del r1
        r2 = ray_tpu.put(jnp.ones(4096, jnp.float32), tensor_transport="collective")
        # Drop the driver's devobj_pull REPLY once: the worker's bounded
        # per-attempt timeout retries (15s attempt cap — was a 60s stall
        # before this round's fix).
        chaos.install({"rules": [{
            "kind": "drop", "method": "devobj_pull", "side": "resp", "times": 1,
        }]}, seed=13)
        t0 = time.perf_counter()
        assert ray_tpu.get(consume.remote(r2), timeout=120) == 4096.0
        results["devobj_handoff_lost_reply_s"] = round(time.perf_counter() - t0, 3)
        chaos.clear()
        del r2
    finally:
        chaos.clear()
        cluster.shutdown()

    # ---- injection-disabled overhead on task_sync (PR 8 methodology) ----
    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024 * 1024)

    @ray_tpu.remote
    def small():
        return b"ok"

    inert_plan = {"rules": [{"kind": "drop", "method": "no_such_method"}]}

    def set_mode(installed: bool):
        if installed:
            chaos.install(inert_plan, seed=1)
        else:
            chaos.clear()

    def block(n):
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(small.remote())
        return n / (time.perf_counter() - t0)

    block(200)  # warm lease + jit paths
    # 150 pairs, like OBSBENCH_r8: short runs on this box swing +-4% while
    # the long-horizon median repeats within ~0.5%.
    pairs = 8 if quick else 150
    block_tasks = 128 if quick else 256
    ratios, off_rates, on_rates = [], [], []
    for i in range(pairs):
        order = [True, False] if i % 2 == 0 else [False, True]
        rates = {}
        for installed in order:
            set_mode(installed)
            rates[installed] = block(block_tasks)
        on_rates.append(rates[True])
        off_rates.append(rates[False])
        ratios.append(rates[False] / rates[True])
    chaos.clear()
    ray_tpu.shutdown()
    results["chaos_off_task_sync_per_s"] = round(statistics.median(off_rates), 1)
    results["chaos_inert_plan_task_sync_per_s"] = round(statistics.median(on_rates), 1)
    results["chaos_inert_plan_overhead_pct"] = round(
        (statistics.median(ratios) - 1.0) * 100.0, 2
    )
    results["chaos_overhead_pairs"] = pairs
    print(
        f"chaos plane: inert-plan overhead {results['chaos_inert_plan_overhead_pct']}% "
        f"(no-plan {results['chaos_off_task_sync_per_s']}/s vs inert "
        f"{results['chaos_inert_plan_task_sync_per_s']}/s over {pairs} ABBA pairs); "
        f"disabled (no plan) is the production arm — its seam cost is one "
        f"is-None check per frame, upper-bounded by the inert-plan arm"
    )


def compute_deltas_vs_prev(results: dict, round_no: int, prev_path: str | None = None):
    """Diff numeric metrics against the previous round's artifact so a
    regression is named IN the artifact, not discovered by a later reviewer
    (VERDICT r5 #8). Keys ending in _per_s count as higher-is-better;
    regressions beyond 5% are listed explicitly."""
    if prev_path is None:
        prev_path = f"MICROBENCH_r{round_no - 1}.json"
    block: dict = {"prev_artifact": prev_path if os.path.exists(prev_path) else None}
    if block["prev_artifact"]:
        with open(prev_path) as f:
            prev = json.load(f)
        deltas = {}
        for key, cur in results.items():
            pv = prev.get(key)
            if (
                isinstance(cur, (int, float))
                and isinstance(pv, (int, float))
                and not isinstance(cur, bool)
                and pv
            ):
                deltas[key] = {"prev": pv, "cur": cur, "pct": round((cur - pv) / pv * 100.0, 1)}
        block["deltas"] = deltas
        block["regressions"] = sorted(
            key
            for key, d in deltas.items()
            if key.endswith("_per_s") and d["pct"] < -5.0
        )
    results["deltas_vs_prev"] = block


def queued_tasks_stress(results, n_tasks):
    """Queue-depth envelope (reference table: 1M+ tasks queued on one node).
    Submission throughput with the queue far beyond execution capacity, then
    a liveness check that the node still schedules."""
    import ray_tpu

    ray_tpu.init(num_cpus=1, object_store_memory=128 * 1024 * 1024)

    @ray_tpu.remote
    def noop():
        return 1

    t0 = time.perf_counter()
    refs = [noop.remote() for _ in range(n_tasks)]
    submit_s = time.perf_counter() - t0
    results["queued_tasks"] = n_tasks
    results["queued_submit_per_s"] = round(n_tasks / submit_s, 1)
    # refs[0] has usually already finished by the end of submission — its
    # latency measures result availability, not liveness.
    t0 = time.perf_counter()
    assert ray_tpu.get(refs[0], timeout=120) == 1
    results["queued_first_result_s"] = round(time.perf_counter() - t0, 3)
    # Liveness under depth: the node must still be scheduling with the queue
    # ~full, proven by draining through the 1000th submitted task (full-queue
    # FIFO drain would take ages; a mid-queue probe shows forward progress).
    probe = min(n_tasks, 1000) - 1
    t0 = time.perf_counter()
    assert ray_tpu.get(refs[probe], timeout=600) == 1
    results["queued_probe_result_s"] = round(time.perf_counter() - t0, 3)
    ray_tpu.shutdown()


def actor_swarm_stress(results, n_actors):
    """Actor-count envelope, sized to this host (reference: 40k across a
    2000-node cluster; one core here). Measures creation + fan-out ping."""
    import ray_tpu

    ray_tpu.init(num_cpus=max(4, n_actors), object_store_memory=128 * 1024 * 1024)

    @ray_tpu.remote(num_cpus=0.01)
    class Swarm:
        def ping(self):
            return os.getpid()

    t0 = time.perf_counter()
    actors = [Swarm.remote() for _ in range(n_actors)]
    pids = ray_tpu.get([a.ping.remote() for a in actors], timeout=1200)
    create_s = time.perf_counter() - t0
    results["actors_created"] = n_actors
    results["actor_processes"] = len(set(pids))
    results["actor_create_per_s"] = round(n_actors / create_s, 2)
    t0 = time.perf_counter()
    ray_tpu.get([a.ping.remote() for a in actors], timeout=600)
    results["actor_fanout_ping_s"] = round(time.perf_counter() - t0, 3)
    ray_tpu.shutdown()


def broadcast_stress(results, mib, n_nodes):
    """100 MiB broadcast across simulated nodes (reference envelope: 1 GiB to
    50+ nodes; binomial-tree push plane)."""
    import numpy as np

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.object_transfer import broadcast_object

    cluster = Cluster()
    try:
        for _ in range(n_nodes):
            cluster.add_node(num_cpus=1, object_store_memory=(mib + 32) * 1024 * 1024)
        cluster.connect()
        cluster.wait_for_nodes()
        import ray_tpu

        data = np.random.default_rng(0).integers(0, 255, mib * 1024 * 1024, dtype=np.uint8)
        ref = ray_tpu.put(data)
        t0 = time.perf_counter()
        pushed = broadcast_object(ref, timeout=1200)
        dt = time.perf_counter() - t0
        results["broadcast_mib"] = mib
        results["broadcast_nodes"] = n_nodes
        results["broadcast_pushed"] = pushed
        results["broadcast_s"] = round(dt, 3)
        results["broadcast_aggregate_mib_per_s"] = round(mib * pushed / dt, 1)
    finally:
        cluster.shutdown()


def many_args_stress(results, n_args):
    """Reference envelope: 10,000+ object args to a single task
    (release/benchmarks/single_node/test_single_node.py test_many_args)."""
    import ray_tpu

    ray_tpu.init(num_cpus=2, object_store_memory=512 * 1024 * 1024)

    @ray_tpu.remote
    def consume(*args):
        return len(args)

    refs = [ray_tpu.put(i) for i in range(n_args)]
    t0 = time.perf_counter()
    assert ray_tpu.get(consume.remote(*refs), timeout=600) == n_args
    results["many_args"] = n_args
    results["many_args_s"] = round(time.perf_counter() - t0, 3)
    ray_tpu.shutdown()


def many_returns_stress(results, n_returns):
    """Reference envelope: 3,000+ returns from a single task
    (test_single_node.py test_many_returns)."""
    import ray_tpu

    ray_tpu.init(num_cpus=2, object_store_memory=512 * 1024 * 1024)

    @ray_tpu.remote
    def produce(n):
        return list(range(n))

    t0 = time.perf_counter()
    refs = produce.options(num_returns=n_returns).remote(n_returns)
    values = ray_tpu.get(refs, timeout=600)
    assert values == list(range(n_returns))
    results["many_returns"] = n_returns
    results["many_returns_s"] = round(time.perf_counter() - t0, 3)
    ray_tpu.shutdown()


def get_many_objects_stress(results, n_objects):
    """Reference envelope: ray.get on 10,000+ store objects in one call
    (test_single_node.py test_ray_get_args)."""
    import ray_tpu

    ray_tpu.init(num_cpus=2, object_store_memory=512 * 1024 * 1024)
    refs = [ray_tpu.put(i) for i in range(n_objects)]
    t0 = time.perf_counter()
    values = ray_tpu.get(refs, timeout=600)
    dt = time.perf_counter() - t0
    assert values == list(range(n_objects))
    results["get_many_objects"] = n_objects
    results["get_many_objects_s"] = round(dt, 3)
    results["get_many_objects_per_s"] = round(n_objects / dt, 1)
    ray_tpu.shutdown()


def shuffle_stress(results, n_rows, n_blocks):
    """Dataset shuffle throughput, pull-based vs push-based (reference:
    push_based_shuffle.py + shuffle nightly suites)."""
    import ray_tpu
    from ray_tpu import data
    from ray_tpu.data.context import DataContext

    ray_tpu.init(num_cpus=4, object_store_memory=512 * 1024 * 1024)
    ctx = DataContext.get_current()
    try:
        # Warmup: spawn the worker pool so the first timed mode doesn't pay
        # cluster cold-start.
        data.range(1000, parallelism=4).random_shuffle(seed=0).count()
        for label, flag in (("pull", False), ("push", True)):
            ctx.use_push_based_shuffle = flag
            t0 = time.perf_counter()
            ds = data.range(n_rows, parallelism=n_blocks).random_shuffle(seed=0)
            assert ds.count() == n_rows
            dt = time.perf_counter() - t0
            results[f"shuffle_{label}_rows_per_s"] = round(n_rows / dt, 1)
        results["shuffle_rows"] = n_rows
        results["shuffle_blocks"] = n_blocks
    finally:
        ctx.use_push_based_shuffle = None
        ray_tpu.shutdown()


def transfer_suite(results, quick=False):
    """--transfer: the ISSUE 10 transfer-plane A/B — cut-through broadcast at
    the r5 shape, pull striping (1 vs 2 replicas), raw-vs-msgpack frame
    framing on a point-to-point push — plus the dispatch-plane regression
    guards (putget_1mib, shuffle_push) the rpc.py changes must not move."""
    import numpy as np

    import ray_tpu
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu._private.transfer_stats import TRANSFER
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.object_transfer import broadcast_object

    io = EventLoopThread.get()

    def oid_for(tag):
        return tag.encode().hex().ljust(56, "0")[:56]

    def seal_raw(node, oid, data):
        offset = io.run(node.store.create(oid, len(data)))
        node.arena.write(offset, data)
        node.store.seal(oid)
        io.run(node.gcs.acall(
            "add_object_location", {"object_id": oid, "node_id": node.node_id}
        ))

    # --- point-to-point push: raw frames vs forced msgpack fallback ---
    mib_p2p = 16 if quick else 64
    cluster = Cluster()
    try:
        nodes = [
            cluster.add_node(num_cpus=1, object_store_memory=(mib_p2p + 64) * 1024 * 1024)
            for _ in range(3)
        ]
        cluster.connect()
        cluster.wait_for_nodes()
        head, n2, n3 = nodes
        payload = np.random.default_rng(0).integers(
            0, 255, mib_p2p * 1024 * 1024, dtype=np.uint8
        ).tobytes()
        # Median of 3 pushes per framing: single pushes swing with this
        # box's multi-second noise bursts (PERF_NOTES measurement traps).
        for label, raw in (("raw", True), ("msgpack", False)):
            n2.raw_frames_enabled = raw
            head.push_manager.raw_enabled = raw
            times = []
            for i in range(3):
                oid = oid_for(f"p2p-{label}-{i}")
                seal_raw(head, oid, payload)
                t0 = time.perf_counter()
                resp = io.run(
                    head.push_manager.push(oid, n2.node_id, n2.address), timeout=600
                )
                times.append(time.perf_counter() - t0)
                assert resp["ok"], resp
                for n in nodes:
                    try:
                        n.store.delete(oid)
                    except Exception:
                        pass
            results[f"push_{label}_mib_per_s"] = round(
                mib_p2p / sorted(times)[len(times) // 2], 1
            )
        n2.raw_frames_enabled = True
        head.push_manager.raw_enabled = True
        results["push_p2p_mib"] = mib_p2p
        results["push_raw_speedup_pct"] = round(
            (results["push_raw_mib_per_s"] / results["push_msgpack_mib_per_s"] - 1)
            * 100.0,
            1,
        )

        # --- pull striping: same object from 1 replica vs 2 replicas ---
        # Loopback on this one-core box has NO per-source parallelism (every
        # in-process "node" shares one IO loop and one CPU), so the striping
        # win is measured over a modeled per-source link: each source serves
        # chunks through a serialized bandwidth gate (asyncio lock + sleep =
        # a NIC at `link_mib_per_s`), which is exactly the resource striping
        # doubles in a real fleet. Unthrottled loopback numbers are recorded
        # alongside for transparency.
        import asyncio as _asyncio

        mib_pull = 8 if quick else 32
        link_mib_per_s = 64
        pdata = np.random.default_rng(1).integers(
            0, 255, mib_pull * 1024 * 1024, dtype=np.uint8
        ).tobytes()

        def throttle(node):
            orig = node.server._handlers["fetch_object_chunk"]
            gate = _asyncio.Lock()

            async def serve(req, _orig=orig, _gate=gate):
                async with _gate:  # one chunk on the "wire" at a time
                    await _asyncio.sleep(
                        req["length"] / (link_mib_per_s * 1024 * 1024)
                    )
                return await _orig(req)

            node.server._handlers["fetch_object_chunk"] = serve
            return orig

        def timed_pull(tag, replicas, throttled):
            origs = [(r, throttle(r)) for r in replicas] if throttled else []
            try:
                times = []
                for i in range(3):
                    oid = oid_for(f"{tag}-{i}")
                    for r in replicas:
                        seal_raw(r, oid, pdata)
                    t0 = time.perf_counter()
                    assert io.run(n3.pull_manager.pull(oid, 300.0), timeout=600)
                    times.append(time.perf_counter() - t0)
                    for n in nodes:
                        try:
                            n.store.delete(oid)
                        except Exception:
                            pass
                return sorted(times)[len(times) // 2]
            finally:
                for r, orig in origs:
                    r.server._handlers["fetch_object_chunk"] = orig

        dt1 = timed_pull("pl1", [head], throttled=True)
        dt2 = timed_pull("pl2", [head, n2], throttled=True)
        lb1 = timed_pull("lb1", [head], throttled=False)
        lb2 = timed_pull("lb2", [head, n2], throttled=False)
        results["pull_mib"] = mib_pull
        results["pull_link_model_mib_per_s"] = link_mib_per_s
        results["pull_1replica_mib_per_s"] = round(mib_pull / dt1, 1)
        results["pull_2replica_mib_per_s"] = round(mib_pull / dt2, 1)
        results["pull_striping_speedup_pct"] = round((dt1 / dt2 - 1) * 100.0, 1)
        results["pull_loopback_1replica_mib_per_s"] = round(mib_pull / lb1, 1)
        results["pull_loopback_2replica_mib_per_s"] = round(mib_pull / lb2, 1)
        results["transfer_chunks_raw"] = TRANSFER.chunks_raw_out
        results["transfer_chunks_msgpack"] = TRANSFER.chunks_msgpack_out
        results["transfer_relays"] = TRANSFER.relays
    finally:
        cluster.shutdown()


def serve_llm_suite(results, quick=False):
    """--serve: the ISSUE 11 continuous-batching load test (SERVEBENCH_r{N}.json;
    r11 also holds a serial-batching arm, which went with its engine option).

    A closed-loop load generator drives the serve.llm engine directly (the
    scheduler IS the claim; the HTTP/SSE envelope above it is exercised by
    tests/test_serve_llm_engine.py): N streams, each submitting a request
    with a shared 32-token system prompt + random suffix and a heavy-tailed
    (geometric — realistic output-length distribution) max_new_tokens,
    reading its token stream to completion, then immediately submitting the
    next: slot-level admission mid-decode + chunked prefill interleave +
    prefix-cache reuse.

    Metrics: p50/p99 TTFT, mean time-per-output-token, aggregate tokens/s
    over the measurement window (keys ``serve_continuous_*``)."""
    import statistics
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine, prefix_route_hint

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=256, max_seq_len=512, dtype=jnp.float32, remat=False,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Oversubscribed offered load (streams > slots): the admission queue is
    # never empty, which is exactly the regime continuous batching targets —
    # a request arriving mid-decode takes the first freed slot.
    streams = 4 if quick else 12
    slots = 8
    duration = 3.0 if quick else 25.0
    block_size = 16
    system = list(range(7, 7 + 32))  # two full blocks shared by every stream
    results["serve_streams"] = streams
    results["serve_slots"] = slots
    results["serve_block_size"] = block_size
    results["serve_prefix_hint"] = prefix_route_hint(system, block_size)[:12]

    def run_arm() -> dict:
        engine = LLMEngine(
            params, cfg, num_slots=slots, block_size=block_size,
            max_model_len=192, prefill_chunk=32,
        )
        try:
            # Warm both compiled programs outside the window.
            engine.submit(system + [1, 2, 3], max_new_tokens=4).result(300)
            stop = threading.Event()
            ttfts, tpots, tokens = [], [], [0]
            t_win = [0.0, 0.0]
            lock = threading.Lock()

            def stream(i):
                rng = np.random.default_rng(1000 + i)
                while not stop.is_set():
                    suffix = rng.integers(0, 256, int(rng.integers(8, 33))).tolist()
                    # Heavy-tailed output length (geometric, mean ~24, tail
                    # to 128 = max_model_len - longest prompt): realistic
                    # LLM completions.
                    n_new = int(min(128, max(4, rng.geometric(1.0 / 24))))
                    t0 = time.perf_counter()
                    req = engine.submit(system + suffix, max_new_tokens=n_new)
                    first = None
                    for _ in req:
                        now = time.perf_counter()
                        if first is None:
                            first = now
                        if stop.is_set() and t_win[1]:
                            break  # window closed; drop the tail
                        with lock:
                            tokens[0] += 1
                    engine.cancel(req)  # no-op unless we broke early
                    if first is not None and not stop.is_set():
                        with lock:
                            ttfts.append(first - t0)
                            n_stream = req.num_generated
                            if n_stream > 1:
                                tpots.append((time.perf_counter() - first) / (n_stream - 1))

            threads = [
                threading.Thread(target=stream, args=(i,), daemon=True)
                for i in range(streams)
            ]
            t_win[0] = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(duration)
            stop.set()
            t_win[1] = time.perf_counter()
            for t in threads:
                t.join(timeout=120)
            wall = t_win[1] - t_win[0]
            st = engine.stats()
            ttfts.sort()

            def pct(xs, p):
                return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else None

            return {
                "tokens_per_s": round(tokens[0] / wall, 1),
                "requests_completed": len(ttfts),
                "ttft_p50_ms": round(1000 * pct(ttfts, 0.50), 1) if ttfts else None,
                "ttft_p99_ms": round(1000 * pct(ttfts, 0.99), 1) if ttfts else None,
                "tpot_mean_ms": round(1000 * statistics.mean(tpots), 2) if tpots else None,
                "preemptions": st["preemptions"],
                "prefix_hit_blocks": st["prefix_hit_blocks"],
                "admitted": st["admitted"],
            }
        finally:
            engine.shutdown()

    arm = run_arm()
    for k, v in arm.items():
        results[f"serve_continuous_{k}"] = v
    print(f"serve[continuous]: {arm}")


def serve_ft_suite(results, quick=False):
    """--serve-ft: self-healing LLM serving (ISSUE 14) — FTBENCH_r{N}.json.

    End to end over a REAL serve instance (cluster + controller + proxy +
    2 LLM replicas), because the claims live in the proxy/controller, not
    the engine:

    - KILL arm: a seeded plan SIGKILLs the serving replica mid-stream (Nth
      actor-call response); the proxy migrates the request with
      resume_tokens= teacher-forced on a live replica. Reported:
      time-to-stream-resume at the CLIENT (the max inter-token gap — the
      kill->first-resumed-token stall dominates it), byte-exactness vs an
      uninterrupted oracle run, dropped streams (must be 0).
    - ROLLING arm, drain ON vs OFF: a closed loop of concurrent streams
      rides a v(n) -> v(n+1) rolling update. Drain ON (default 30s bound)
      retires old replicas only after their streams finish: zero drops AND
      zero forced migrations. Drain OFF (drain_timeout_s=0, the pre-ISSUE
      behavior) kills old replicas under live streams: the streams only
      survive because the MIGRATION path catches them — visible as forced
      migrations + a fatter p99 inter-token stall.
    """
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.serve._private.common import PREFIX_HINT_HEADER
    from ray_tpu.serve.llm import LLMDeployment, prefix_route_hint

    model = dict(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=48, max_seq_len=64, dtype="float32", remat=False,
    )
    engine_cfg = dict(num_slots=4, block_size=4, max_model_len=64, prefill_chunk=4)
    n_tokens = 16 if quick else 32
    results["serve_ft_tokens_per_stream"] = n_tokens

    def oracle(prompt, n):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import TransformerConfig, init_params
        from ray_tpu.serve.llm import LLMEngine

        kw = dict(model)
        kw["dtype"] = jnp.dtype(kw["dtype"]).type
        cfg = TransformerConfig(**kw)
        eng = LLMEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, **engine_cfg)
        try:
            return eng.submit(prompt, max_new_tokens=n).result(120)
        finally:
            eng.shutdown()

    def stream(url, body, headers=None, timeout=240):
        """Returns (tokens, done, [arrival stamps])."""
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(), headers=headers or {}
        )
        resp = urllib.request.urlopen(req, timeout=timeout)
        toks, stamps, buf = [], [], b""
        while True:
            chunk = resp.read(64)
            if not chunk:
                return toks, False, stamps
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                payload = event[6:]
                if payload == b"[DONE]":
                    return toks, True, stamps
                toks.append(json.loads(payload)["token"])
                stamps.append(time.perf_counter())

    def deploy(version, drain_timeout_s=30.0):
        app = serve.deployment(
            num_replicas=2, version=version, drain_timeout_s=drain_timeout_s
        )(LLMDeployment).bind(model, engine_config=dict(engine_cfg))
        serve.run(app, route_prefix="/llm")

    def replica_actors():
        from ray_tpu.serve._private.common import CONTROLLER_NAME

        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        table = ray_tpu.get(controller.get_routing_table.remote(-2, 0.1))["table"]
        return [r["actor_name"] for r in table.get("LLMDeployment", {}).get("replicas", [])]

    def flight_count(cluster, kind, since):
        io = EventLoopThread.get()
        resp = io.run(cluster.nodes[0].rpc_debug_dump({}), timeout=15)
        return sum(
            1
            for proc in resp.get("processes", [])
            for ev in proc.get("events", [])
            if ev.get("type") == kind and ev.get("ts", 0) >= since - 1.0
        )

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=6, object_store_memory=96 * 1024 * 1024)
        cluster.connect()
        cluster.wait_for_nodes()
        serve.start()
        deploy("v1")
        host, port = serve.http_address()
        url = f"http://{host}:{port}/llm"

        # ---- KILL arm: seeded mid-stream replica kill -> migration ----
        import zlib

        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        expect = oracle(prompt, n_tokens)
        stream(url, dict(tokens=prompt, max_new_tokens=4))  # warm both paths
        t_since = time.time()
        hint = prefix_route_hint(prompt, engine_cfg["block_size"])
        actors = replica_actors()
        victim = actors[zlib.crc32(hint.encode()) % len(actors)]
        assert cluster.install_plan_in_actor(
            victim,
            {"rules": [{"kind": "kill", "method": ["actor_call"],
                        "side": "resp", "after": 2, "times": 1}]},
            seed=13,
        )
        t0 = time.perf_counter()
        toks, done, stamps = stream(
            url, dict(tokens=prompt, max_new_tokens=n_tokens),
            headers={PREFIX_HINT_HEADER: hint},
        )
        gaps = [b - a for a, b in zip(stamps, stamps[1:])] or [0.0]
        results["kill_stream_ok"] = bool(done and toks == expect)
        results["kill_stream_wall_s"] = round(time.perf_counter() - t0, 3)
        results["kill_time_to_stream_resume_s"] = round(max(gaps), 3)
        results["kill_median_token_gap_ms"] = round(
            1000 * sorted(gaps)[len(gaps) // 2], 2
        )
        results["kill_migrations"] = flight_count(cluster, "llm_migrate", t_since)
        results["kill_chaos_kills"] = flight_count(cluster, "chaos_kill", t_since)
        print(
            f"serve-ft[kill]: ok={results['kill_stream_ok']} "
            f"resume={results['kill_time_to_stream_resume_s']}s "
            f"migrations={results['kill_migrations']}"
        )
        # Let the controller finish replacing the victim before the next arm.
        deadline = time.monotonic() + 120
        while len(replica_actors()) < 2 and time.monotonic() < deadline:
            time.sleep(0.25)

        # ---- ROLLING arm: drain ON vs OFF under a closed loop ----
        def rolling_arm(label, old_version, new_version, drain_timeout_s):
            # (Re)deploy the old version with the arm's drain config, then
            # roll under load.
            deploy(old_version, drain_timeout_s=drain_timeout_s)
            rng = np.random.default_rng(5)
            prompts = [rng.integers(0, 64, 6).tolist() for _ in range(3)]
            oracles = [oracle(p, n_tokens) for p in prompts]
            t_since = time.time()
            stop = threading.Event()
            drops, completions, corrupt = [], [0], []
            gaps_all: list = []
            lock = threading.Lock()

            def loop(i):
                while not stop.is_set():
                    try:
                        toks, done, stamps = stream(
                            url, dict(tokens=prompts[i], max_new_tokens=n_tokens)
                        )
                        if not done:
                            drops.append(i)
                            return
                        if toks != oracles[i]:
                            corrupt.append(i)
                            return
                        with lock:
                            completions[0] += 1
                            gaps_all.extend(
                                b - a for a, b in zip(stamps, stamps[1:])
                            )
                    except Exception as e:  # noqa: BLE001
                        drops.append(f"{i}:{type(e).__name__}")
                        return

            threads = [
                threading.Thread(target=loop, args=(i,), daemon=True)
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 60
            while completions[0] < 2 and not drops and time.monotonic() < deadline:
                time.sleep(0.05)
            t_roll = time.perf_counter()
            deploy(new_version, drain_timeout_s=drain_timeout_s)
            roll_wall = time.perf_counter() - t_roll
            time.sleep(1.0)
            stop.set()
            for t in threads:
                t.join(timeout=300)
            gaps_all.sort()
            p99 = gaps_all[min(len(gaps_all) - 1, int(0.99 * len(gaps_all)))] if gaps_all else 0.0
            out = {
                "dropped_streams": len(drops) + len(corrupt),
                "completed_streams": completions[0],
                "rolling_update_wall_s": round(roll_wall, 2),
                "stall_p99_ms": round(1000 * p99, 1),
                "max_stall_ms": round(1000 * (gaps_all[-1] if gaps_all else 0.0), 1),
                "migrations": flight_count(cluster, "llm_migrate", t_since),
                "drains_recorded": flight_count(cluster, "replica_drain", t_since),
            }
            for k, v in out.items():
                results[f"rolling_{label}_{k}"] = v
            print(f"serve-ft[rolling-{label}]: {out}")

        if not quick:
            rolling_arm("drain", "v2", "v3", drain_timeout_s=30.0)
            rolling_arm("nodrain", "v4", "v5", drain_timeout_s=0.0)
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def serve_disagg_suite(results, quick=False):
    """--serve-disagg: prefill/decode disaggregation + cluster prefix tier
    (ISSUE 20) — DISAGGBENCH_r{N}.json.

    End to end over a REAL serve instance (cluster + controller + proxy),
    because the claim lives in the pool split, not the engine: under MIXED
    load — long-prefill streams (384-token prompts on a compute-bound
    model, 4 output tokens: pure prefill pressure) interleaved with
    short-decode streams (48-token prompts, 12 output tokens: the
    latency-sensitive traffic) — the
    monolithic arm makes every short stream's prefill queue FIFO behind
    whatever long prefill its replica is already chewing, while the
    disaggregated arm routes prefills to a dedicated pool (where SJF lets
    shorts jump the queue), seals the KV as a device object, and hands the
    ~300B descriptor to an uncontended decode pool over direct-mailbox p2p.

    Arms at EQUAL replica budget (4 engines each):
    - mono:   4 replicas, role "both" — continuous batching, no handoff.
    - disagg: 2 prefill + 2 decode replicas with the cluster prefix tier ON
              (2 prefill replicas so the registry actually cross-imports:
              a replica skips its own published rows).

    Per arm: p50/p99 TTFT of the SHORT streams, aggregate tokens/s across
    all streams, completed-request counts. The disagg arm also records the
    deterministic evidence: KV handoff count (decode-side imports, flight
    + engine counters agreeing), cluster-prefix import hits (>0 — seeded
    by a serial warm round-robining the shared system prefix over both
    prefill replicas), host-store object delta over the measured window
    (0: descriptors ride actor RPC, payloads ride direct mailboxes), and
    the leak oracle — every engine's free+cached block count restored to
    pool size after the load quiesces."""
    import statistics
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import worker_context
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.serve.llm import LLMDeployment, disaggregated_llm_app

    if quick:
        # Machinery smoke: a dispatch-bound tiny model CANNOT show the TTFT
        # story on this box (prefill costs less than one HTTP hop, so the
        # handoff's fixed overhead dominates) — the quick pass only proves
        # the plumbing: handoffs flow, prefix tier hits, zero store delta,
        # zero leaked blocks. Ratio certification lives in the full sweep.
        model = dict(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=48, max_seq_len=160, dtype="float32", remat=False,
        )
        engine_cfg = dict(
            num_slots=4, block_size=4, max_model_len=160, prefill_chunk=8
        )
        system = list(range(5, 5 + 16))  # 4 full blocks shared by every stream
        long_prompt_len, short_new = 96, 12
        n_long, n_short = 2, 2
        duration = 5.0
    else:
        # Full sweep: a COMPUTE-bound model (a 384-token prefill costs
        # hundreds of ms of matmul on this box — far above the per-hop
        # dispatch cost), so a short stream queued FIFO behind a long
        # prefill in the monolithic arm pays real latency, which is the
        # regime disaggregation (SJF prefill pool + uncontended decode
        # pool) targets.
        model = dict(
            vocab_size=128, d_model=256, n_layers=6, n_heads=4, n_kv_heads=2,
            d_ff=1536, max_seq_len=512, dtype="float32", remat=False,
        )
        engine_cfg = dict(
            num_slots=4, block_size=16, max_model_len=448, prefill_chunk=16
        )
        system = list(range(5, 5 + 32))  # 2 full blocks shared by every stream
        long_prompt_len, short_new = 384, 12
        n_long, n_short = 4, 4
        duration = 25.0
    vocab = model["vocab_size"]
    suffix_len = len(system) // 2
    results.update(
        disagg_streams_long=n_long,
        disagg_streams_short=n_short,
        disagg_long_prompt_tokens=long_prompt_len,
        disagg_short_prompt_tokens=len(system) + suffix_len,
        disagg_short_new_tokens=short_new,
        disagg_window_s=duration,
        disagg_replicas={"mono": 4, "prefill": 2, "decode": 2},
        disagg_model={k: v for k, v in model.items() if k != "dtype"},
    )

    def stream(url, body, timeout=240):
        """Returns (tokens, done, [arrival stamps])."""
        req = urllib.request.Request(url, data=json.dumps(body).encode())
        resp = urllib.request.urlopen(req, timeout=timeout)
        toks, stamps, buf = [], [], b""
        while True:
            chunk = resp.read(64)
            if not chunk:
                return toks, False, stamps
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                payload = event[6:]
                if payload == b"[DONE]":
                    return toks, True, stamps
                toks.append(json.loads(payload)["token"])
                stamps.append(time.perf_counter())

    def flight_count(cluster, kind, since):
        io = EventLoopThread.get()
        resp = io.run(cluster.nodes[0].rpc_debug_dump({}), timeout=15)
        return sum(
            1
            for proc in resp.get("processes", [])
            for ev in proc.get("events", [])
            if ev.get("type") == kind and ev.get("ts", 0) >= since - 1.0
        )

    def replica_stats(dep_names):
        from ray_tpu.serve._private.common import CONTROLLER_NAME

        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        table = ray_tpu.get(controller.get_routing_table.remote(-2, 0.1))["table"]
        out = {}
        for dep in dep_names:
            stats = []
            for r in table.get(dep, {}).get("replicas", []):
                a = ray_tpu.get_actor(r["actor_name"])
                stats.append(
                    ray_tpu.get(
                        a.handle_request.remote("get_stats", (), {}), timeout=30
                    )
                )
            out[dep] = stats
        return out

    def pct(xs, p):
        return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else None

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=12, object_store_memory=96 * 1024 * 1024)
        cluster.connect()
        cluster.wait_for_nodes()
        cw = worker_context.get_core_worker()

        def store_objects() -> int:
            return cw.raylet.call("get_state")["store"]["num_objects"]

        def run_arm(label, deploy_fn, dep_names):
            serve.start()
            deploy_fn()
            host, port = serve.http_address()
            url = f"http://{host}:{port}/llm"
            # Warm every compiled program AND (disagg) seed the cluster
            # prefix tier deterministically: 4 serial shared-prefix shorts
            # round-robin over both prefill replicas, so replica B's probe
            # finds replica A's published system-prefix row. One long warms
            # the long-prompt prefill shape.
            t_since = time.time()
            rng = np.random.default_rng(7)
            for i in range(4):
                suffix = rng.integers(0, vocab, suffix_len).tolist()
                toks, done, _ = stream(
                    url, dict(tokens=system + suffix, max_new_tokens=4)
                )
                assert done and len(toks) == 4, (label, i, toks, done)
            stream(
                url,
                dict(
                    tokens=system
                    + rng.integers(0, vocab, long_prompt_len - len(system)).tolist(),
                    max_new_tokens=2,
                ),
            )
            store_before = store_objects()
            stop = threading.Event()
            lock = threading.Lock()
            short_ttfts: list = []
            counts = {"tokens": 0, "short_done": 0, "long_done": 0, "errors": 0}

            def short_loop(i):
                srng = np.random.default_rng(100 + i)
                while not stop.is_set():
                    suffix = srng.integers(0, vocab, suffix_len).tolist()
                    t0 = time.perf_counter()
                    try:
                        toks, done, stamps = stream(
                            url, dict(tokens=system + suffix, max_new_tokens=short_new)
                        )
                    except Exception:
                        with lock:
                            counts["errors"] += 1
                        return
                    if not done:
                        continue
                    with lock:
                        counts["tokens"] += len(toks)
                        if not stop.is_set():
                            counts["short_done"] += 1
                            short_ttfts.append(stamps[0] - t0)

            def long_loop(i):
                lrng = np.random.default_rng(200 + i)
                while not stop.is_set():
                    body = lrng.integers(
                        0, vocab, long_prompt_len - len(system)
                    ).tolist()
                    try:
                        toks, done, _ = stream(
                            url, dict(tokens=system + body, max_new_tokens=4)
                        )
                    except Exception:
                        with lock:
                            counts["errors"] += 1
                        return
                    with lock:
                        counts["tokens"] += len(toks)
                        if done and not stop.is_set():
                            counts["long_done"] += 1

            threads = [
                threading.Thread(target=long_loop, args=(i,), daemon=True)
                for i in range(n_long)
            ] + [
                threading.Thread(target=short_loop, args=(i,), daemon=True)
                for i in range(n_short)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(duration)
            stop.set()
            for t in threads:
                t.join(timeout=300)
            wall = time.perf_counter() - t0
            assert counts["errors"] == 0, (label, counts)
            short_ttfts.sort()
            arm = {
                "tokens_per_s": round(counts["tokens"] / wall, 1),
                "short_completed": counts["short_done"],
                "long_completed": counts["long_done"],
                "short_ttft_p50_ms": round(1000 * pct(short_ttfts, 0.50), 1)
                if short_ttfts
                else None,
                "short_ttft_p99_ms": round(1000 * pct(short_ttfts, 0.99), 1)
                if short_ttfts
                else None,
                "short_ttft_mean_ms": round(1000 * statistics.mean(short_ttfts), 1)
                if short_ttfts
                else None,
            }
            # Handoff-path host-store evidence: descriptors ride actor RPC,
            # KV payloads ride direct mailboxes — the measured window must
            # add NOTHING to the node's shm store (bounded settle for the
            # proxy's async stream-buffer frees).
            deadline = time.monotonic() + 30
            delta = store_objects() - store_before
            while delta > 0 and time.monotonic() < deadline:
                time.sleep(0.25)
                delta = store_objects() - store_before
            arm["store_objects_delta"] = delta
            # Leak oracle: every engine's KV pool back to full (free blocks
            # + resident prefix-cache blocks == pool size) once idle.
            deadline = time.monotonic() + 30
            while True:
                stats = replica_stats(dep_names)
                leak = sum(
                    s["num_blocks"] - s["free_blocks"] - s["cached_blocks"]
                    for ss in stats.values()
                    for s in ss
                )
                if leak == 0 or time.monotonic() > deadline:
                    break
                time.sleep(0.25)
            arm["kv_leak_blocks"] = leak
            for k, v in arm.items():
                results[f"{label}_{k}"] = v
            print(f"serve-disagg[{label}]: {arm}")
            return stats, t_since

        # ---- mono arm: 4 role-"both" replicas, no pools ----
        def deploy_mono():
            app = serve.deployment(num_replicas=4, name="llm")(LLMDeployment).bind(
                model_config=model, engine_config=dict(engine_cfg)
            )
            serve.run(app, route_prefix="/llm")

        mono_stats, _ = run_arm("mono", deploy_mono, ["llm"])
        assert all(s["handoffs"] == 0 for s in mono_stats["llm"]), mono_stats
        serve.shutdown()

        # ---- disagg arm: 2 prefill + 2 decode, cluster prefix tier ON ----
        def deploy_disagg():
            serve.run(
                disaggregated_llm_app(
                    model,
                    dict(engine_cfg),
                    name="llm",
                    prefill_replicas=2,
                    decode_replicas=2,
                    cluster_prefix=True,
                )
            )

        disagg_stats, t_since = run_arm(
            "disagg", deploy_disagg, ["llm", "llm--prefill"]
        )
        dec = disagg_stats["llm"]
        pre = disagg_stats["llm--prefill"]
        results["disagg_handoffs"] = sum(s["handoffs"] for s in dec)
        results["disagg_handoff_exports"] = sum(s["handoff_exports"] for s in pre)
        results["disagg_handoff_failed"] = sum(
            s["handoff_failed"] for s in dec + pre
        )
        results["disagg_prefix_import_hits"] = sum(
            s["prefix_import_hits"] for s in pre
        )
        results["disagg_prefix_import_misses"] = sum(
            s["prefix_import_misses"] for s in pre
        )
        results["disagg_published_prefixes"] = sum(
            s["published_prefixes"] for s in pre
        )
        results["disagg_handoff_flight_events"] = flight_count(
            cluster, "llm_kv_handoff", t_since
        )
        results["disagg_prefix_import_flight_events"] = flight_count(
            cluster, "llm_prefix_import", t_since
        )
        # Pool-role hygiene: decode replicas never prefill-published, and
        # every completed stream rode a handoff (no silent mono fallback).
        assert all(s["role"] == "decode" for s in dec), dec
        assert all(s["role"] == "prefill" for s in pre), pre
        assert results["disagg_handoffs"] > 0, results
        assert results["disagg_prefix_import_hits"] > 0, results
        assert results["disagg_store_objects_delta"] == 0, results
        serve.shutdown()

        if results.get("mono_short_ttft_p99_ms") and results.get(
            "disagg_short_ttft_p99_ms"
        ):
            results["disagg_short_ttft_p99_reduction_pct"] = round(
                (
                    1
                    - results["disagg_short_ttft_p99_ms"]
                    / results["mono_short_ttft_p99_ms"]
                )
                * 100.0,
                1,
            )
        if results.get("mono_tokens_per_s"):
            results["disagg_tokens_vs_mono"] = round(
                results["disagg_tokens_per_s"] / results["mono_tokens_per_s"], 2
            )
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def putget_guard(results, duration):
    """1 MiB object-plane regression guard for the --transfer artifact: the
    rpc.py wire changes must not move the dispatch/store hot path.

    Methodology matches MICROBENCH_r5's basic_suite exactly (fresh cluster,
    ONE `duration`-second window of put then one of putget) so the numbers
    are comparable; the whole guard repeats 3× in a fresh cluster each time
    and reports the best window per metric — this box's noise is
    non-stationary multi-second bursts (PERF_NOTES measurement traps) that
    swing single windows ±30%, and repeating windows WITHIN one cluster is
    not an option: every extra put window leaves thousands of freed 1 MiB
    objects whose arena churn taxes the following putget window (cost a
    confusing hour in r10)."""
    import numpy as np

    import ray_tpu

    best_put, best_putget = 0.0, 0.0
    for _ in range(3):
        ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
        arr = np.zeros(1024 * 1024, dtype=np.uint8)
        best_put = max(best_put, timeit(lambda: ray_tpu.put(arr), duration))
        best_putget = max(
            best_putget, timeit(lambda: ray_tpu.get(ray_tpu.put(arr)), duration)
        )
        ray_tpu.shutdown()
    results["put_1mib_per_s"] = round(best_put, 1)
    results["putget_1mib_per_s"] = round(best_putget, 1)


def sim_suite(results, quick=False):
    """--sim: control-plane scale bench over simnode shells
    (SIMBENCH_r{N}.json). Four measurement families:

    1. NODE-COUNT SWEEP, before/after arms: boot + view-convergence time,
       stub-task throughput, p99 placement latency, and per-interval
       heartbeat view bytes with versioned delta sync ON vs the legacy
       full-view reply. The legacy arm's bytes/interval grow O(N) per
       raylet (O(N^2) cluster-wide); the delta arm's steady state is ~0 —
       the sub-quadratic evidence the acceptance gate asks for.
    2. NODE-DEATH directory cost: _on_node_death wall time over a seeded
       location table, per-node index vs legacy full scan.
    3. LOCALITY arms: fraction of reference-arg tasks landing on a holder
       with locality_aware_scheduling on vs off (the no-locality arm is
       the measured baseline, not a thought experiment).
    4. TASK-EVENT ingest: wire-path flood against the drop-oldest ring —
       ingest rate, ring bound honored, dropped counter.

    Plus the seeded sim-scale chaos SLO scorecard (tests/chaos_matrix.py
    run_sim_matrix). Everything runs in THIS process: shells are simnode
    shells, executors are stubs on a modeled clock (PARITY.md scale row).
    """
    import asyncio
    import statistics

    from ray_tpu._private.simnode import SimCluster, SimTraffic, _percentile

    window_s = 2.5 if quick else 4.0
    # The legacy arm's reply encode is O(N) per heartbeat: at 1000 shells
    # it saturates the loop outright (which IS the finding), so the
    # before-arm stops at 512 — the 64->512 curve establishes the growth —
    # while the delta arm runs through 1000. Heartbeat cadence relaxes
    # with N (real deployments do the same); the per-INTERVAL accounting
    # is cadence-normalized so arms stay comparable.
    if quick:
        sweep = [(64, ("delta", "legacy")), (128, ("delta", "legacy"))]
    else:
        # Legacy (full-view) arm is capped at 256 shells: at 512 the
        # O(N^2) reply traffic starves the burst loop past its 300 s
        # timeout on a single box — the collapse is already evidenced by
        # the 128->256 legacy rows (tasks/s 985 -> 115). Record the cap
        # in the artifact rather than truncating silently.
        sweep = [
            (128, ("delta", "legacy")),
            (256, ("delta", "legacy")),
            (512, ("delta",)),
            (1000, ("delta",)),
        ]
        results["sim_sweep_notes"] = (
            "legacy arm capped at 256 nodes: full-view replies at 512 "
            "shells exceed single-box capacity (task burst stalls past "
            "300 s); quadratic growth is evidenced by the 128->256 "
            "legacy rows, delta arms continue to 1000 nodes"
        )
    results["sim_sweep"] = {}

    for n_nodes, arms in sweep:
        hb_s = 0.25 if n_nodes <= 256 else 0.5
        for arm in arms:
            key = f"n{n_nodes}_{arm}"
            cfg = {
                "heartbeat_interval_s": hb_s,
                "node_death_timeout_s": 10.0,
                "heartbeat_delta_sync": arm == "delta",
            }
            t0 = time.perf_counter()
            c = SimCluster(
                n_nodes, resources_per_node={"CPU": 8},
                num_entry_nodes=16, _system_config=cfg,
            )
            c.start()
            boot_s = time.perf_counter() - t0
            c.wait_for_view(timeout=120)
            view_s = time.perf_counter() - t0

            # Heartbeat accounting over an idle window: what does merely
            # EXISTING at this scale cost the GCS reply path per interval?
            c.gcs.hb_stats = {
                "replies": 0, "rows": 0, "full_replies": 0, "view_bytes": 0,
            }
            c.gcs.hb_account = True
            time.sleep(window_s)
            c.gcs.hb_account = False
            hb = dict(c.gcs.hb_stats)
            intervals = max(1, round(window_s / hb_s))
            per_interval_bytes = hb["view_bytes"] / intervals
            per_interval_rows = hb["rows"] / intervals

            # Stub-task burst: throughput + placement tail over the real
            # submit wire.
            n_tasks = 2000 if quick else 5000
            t1 = time.perf_counter()

            async def _burst(cluster=c, total=n_tasks):
                step = 500
                for i in range(0, total, step):
                    await asyncio.gather(
                        *[
                            cluster.asubmit(cluster.make_spec(sim_ms=1.0))
                            for _ in range(step)
                        ]
                    )

            c._io.run(_burst(), timeout=300)
            assert c.wait_done(n_tasks, timeout=180), f"{key}: burst stalled"
            burst_s = time.perf_counter() - t1
            lat = c.placement_latencies()
            row = {
                "nodes": n_nodes,
                "arm": arm,
                "hb_interval_s": hb_s,
                "boot_s": round(boot_s, 2),
                "view_converge_s": round(view_s, 2),
                "hb_replies": hb["replies"],
                "hb_full_replies": hb["full_replies"],
                "hb_view_rows_per_interval": round(per_interval_rows, 1),
                "hb_view_bytes_per_interval": round(per_interval_bytes, 1),
                "hb_view_bytes_per_node_per_interval": round(
                    per_interval_bytes / n_nodes, 2
                ),
                "tasks": n_tasks,
                "tasks_per_s": round(n_tasks / burst_s, 1),
                "placement_p50_ms": round(_percentile(lat, 0.50) * 1000, 2),
                "placement_p99_ms": round(_percentile(lat, 0.99) * 1000, 2),
            }
            c.shutdown()
            results["sim_sweep"][key] = row
            print(f"  sim sweep {key}: {row}")

    # ---- node-death directory cost: per-node index vs full scan ----
    n_objects = 5000 if quick else 20000
    death = {}
    for arm in ("index", "scan"):
        cfg = {
            "heartbeat_interval_s": 0.5,
            "node_death_timeout_s": 60.0,
            "gcs_location_index": arm == "index",
        }
        c = SimCluster(
            64, resources_per_node={"CPU": 8}, _system_config=cfg,
        )
        c.start()
        c.wait_for_view(timeout=60)
        victim = c.nodes[-1]

        async def _seed(cluster=c, victim_node=victim, total=n_objects):
            gcs = cluster.nodes[0].gcs
            for i in range(total):
                node = (
                    victim_node
                    if i % 8 == 0
                    else cluster.nodes[i % (len(cluster.nodes) - 1)]
                )
                await gcs.acall(
                    "add_object_location",
                    {"object_id": f"{i:056x}", "node_id": node.node_id},
                )

        c._io.run(_seed(), timeout=300)
        t0 = time.perf_counter()
        c._io.run(c.gcs._on_node_death(victim.node_id), timeout=60)
        death[arm] = {
            "on_node_death_ms": round((time.perf_counter() - t0) * 1000, 2),
            "location_rows": n_objects,
            "victim_rows": n_objects // 8,
        }
        c.shutdown()
    results["sim_node_death"] = death
    print(f"  sim node death: {death}")

    # ---- locality arms ----
    loc = {}
    n_ref_tasks = 120 if quick else 400
    for arm in ("locality", "no_locality"):
        cfg = {
            "heartbeat_interval_s": 0.2,
            "node_death_timeout_s": 60.0,
            "locality_aware_scheduling": arm == "locality",
        }
        c = SimCluster(
            128 if not quick else 64,
            resources_per_node={"CPU": 8},
            num_entry_nodes=8,
            _system_config=cfg,
        )
        c.start()
        c.wait_for_view(timeout=60)
        holders = c.nodes[32:48]
        oids = []
        for i, h in enumerate(holders):
            oid = f"b{i:055x}"
            c.seed_object(h, oid)
            oids.append((oid, h.node_id))
        time.sleep(0.5)  # let holder rows settle into entry views

        async def _ref_burst(cluster=c, pairs=oids, total=n_ref_tasks):
            futs = []
            for i in range(total):
                oid, _holder = pairs[i % len(pairs)]
                spec = cluster.make_spec(
                    args=[("r", oid, None)], sim_ms=2.0
                )
                fut = cluster.register_waiter(spec.task_id)
                await cluster.asubmit(spec)
                futs.append((spec.task_id, fut, pairs[i % len(pairs)][1]))
            hits = 0
            for tid, fut, holder_nid in futs:
                landed = await asyncio.wait_for(fut, 30)
                if landed == holder_nid:
                    hits += 1
            return hits

        hits = c._io.run(_ref_burst(), timeout=180)
        lat = c.placement_latencies()
        loc[arm] = {
            "ref_tasks": n_ref_tasks,
            "holder_hits": hits,
            "holder_hit_frac": round(hits / n_ref_tasks, 3),
            "locality_hit_events": sum(n.locality_hits for n in c.nodes),
            "placement_p99_ms": round(_percentile(lat, 0.99) * 1000, 2),
        }
        c.shutdown()
    results["sim_locality"] = loc
    print(f"  sim locality: {loc}")

    # ---- task-event ingest flood vs the drop-oldest ring ----
    from ray_tpu._private.rpc import RpcClient

    cfg = {
        "heartbeat_interval_s": 0.5,
        "task_events_buffer_size": 2048,
    }
    c = SimCluster(8, _system_config=cfg)
    c.start()
    cli = RpcClient(c.gcs.address, label="simbench-events")
    n_events = 20000 if quick else 100000
    batch = 1000
    t0 = time.perf_counter()

    async def _flood(total=n_events, step=batch, client=cli):
        for i in range(0, total, step):
            evs = [
                {"task_id": f"e{j:014d}", "state": "FINISHED", "ts": 0.0}
                for j in range(i, i + step)
            ]
            await client.acall("record_task_events", {"events": evs})

    c._io.run(_flood(), timeout=300)
    flood_s = time.perf_counter() - t0
    results["sim_task_events"] = {
        "events_sent": n_events,
        "ingest_events_per_s": round(n_events / flood_s, 1),
        "ring_size_after": len(c.gcs.task_events),
        "ring_maxlen": c.gcs.task_events.maxlen,
        "events_dropped_total": c.gcs.events_dropped_total,
    }
    assert len(c.gcs.task_events) <= c.gcs.task_events.maxlen
    assert c.gcs.events_dropped_total == n_events - c.gcs.task_events.maxlen
    cli.close()
    c.shutdown()
    print(f"  sim task events: {results['sim_task_events']}")

    # ---- sim-scale chaos SLO scorecard ----
    import sys as _sys

    tests_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
    if tests_dir not in _sys.path:
        _sys.path.insert(0, tests_dir)
    from chaos_matrix import run_sim_matrix

    cells = run_sim_matrix(num_nodes=96, seed=7, quick=quick)
    results["sim_slo_scorecard"] = [r.summary() for r in cells]
    results["sim_slo_ok"] = all(r.ok for r in cells)
    print(f"  sim SLO scorecard ok={results['sim_slo_ok']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--round", type=int, default=int(os.environ.get("GRAFT_ROUND", "2")))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny-count CPU-only sanity pass (<30s): basic suite only, "
        "nonzero exit on any error — invoked from tier-1 so dispatch-path "
        "breakage fails pytest instead of the next bench round",
    )
    ap.add_argument(
        "--hop-budget",
        action="store_true",
        help="measure and print the per-hop dispatch latency budget "
        "(warm lease vs direct actor vs classic raylet path)",
    )
    ap.add_argument(
        "--recorder-overhead",
        action="store_true",
        help="measure the always-on flight-recorder + sampled-hop-stamp cost "
        "on task_sync (paired ABBA windows, one cluster; OBSBENCH_r{N}.json)",
    )
    ap.add_argument(
        "--device-objects",
        action="store_true",
        help="device-ref handoff vs host-shm put/get at 1 MiB / 32 MiB "
        "(same-process zero-copy + actor→actor collective handoff); records "
        "DEVBENCH_r{N}.json with the zero-shm-copy evidence",
    )
    ap.add_argument(
        "--dag",
        action="store_true",
        help="classic dag.execute() vs compiled execution on a 4-stage "
        "actor pipeline; records DAGBENCH_r{N}.json with the zero-RPC/"
        "zero-ref evidence and per-stage hop stamps",
    )
    ap.add_argument(
        "--pipeline",
        action="store_true",
        help="MPMD pipeline over compiled graphs (ISSUE 12): 4-stage "
        "descriptor-channel pipeline vs classic-dispatch actor pipeline "
        "(device-object and host arms) and single-controller "
        "pipeline_apply, with bubble fraction at M in {4,16} and the "
        "zero-RPC / zero-host-copy counters; records PIPEBENCH_r{N}.json",
    )
    ap.add_argument(
        "--serve",
        action="store_true",
        help="continuous-batching LLM serving (ISSUE 11): closed-loop load "
        "generator at N concurrent streams against the engine — p50/p99 "
        "TTFT, time-per-output-token, aggregate tokens/s; records "
        "SERVEBENCH_r{N}.json",
    )
    ap.add_argument(
        "--serve-ft",
        dest="serve_ft",
        action="store_true",
        help="self-healing serving (ISSUE 14): time-to-stream-resume after "
        "a seeded mid-stream replica kill (migration + teacher-forced "
        "resume), and rolling-update dropped-stream counts with drain ON "
        "vs OFF; records FTBENCH_r{N}.json",
    )
    ap.add_argument(
        "--serve-disagg",
        dest="serve_disagg",
        action="store_true",
        help="prefill/decode disaggregation + cluster KV prefix tier "
        "(ISSUE 20): mixed long-prefill/short-decode closed-loop load, "
        "monolithic 4-replica arm vs 2-prefill+2-decode pools — short-"
        "stream p99 TTFT, aggregate tokens/s, KV handoff + cluster-prefix-"
        "import counters, zero-host-store handoff evidence; records "
        "DISAGGBENCH_r{N}.json",
    )
    ap.add_argument(
        "--chaos",
        action="store_true",
        help="chaos-plane recovery budgets (ISSUE 13): pull failover under "
        "mid-frame reset, devobj handoff under a lost pull reply, broadcast "
        "under relay partition, acall heal-after-partition, plus the "
        "injection-disabled overhead check on task_sync; records "
        "CHAOSBENCH_r{N}.json",
    )
    ap.add_argument(
        "--sim",
        action="store_true",
        help="control-plane scale bench (ISSUE 19): node-count sweep over "
        "simnode raylet shells with heartbeat delta-sync before/after arms "
        "(per-interval view bytes), node-death directory cost index vs "
        "scan, locality vs no-locality placement arms, task-event ingest "
        "flood, and the seeded sim-scale chaos SLO scorecard; records "
        "SIMBENCH_r{N}.json",
    )
    ap.add_argument(
        "--collective",
        action="store_true",
        help="group-broadcast weight-sync A/B (ISSUE 15): device-object "
        "broadcast vs K-serial-unicast at fleet sizes K, latency + "
        "aggregate MiB/s, zero-host-store evidence, and an end-to-end "
        "Podracer IMPALA iterations/s row; plus (ISSUE 16) relay-tree vs "
        "flat broadcast under a modeled egress link and the tree-allreduce "
        "bit-exact oracle sweep; records COLLBENCH_r{N}.json",
    )
    ap.add_argument(
        "--tree",
        action="store_true",
        help="with --collective: run only the relay-TREE broadcast arm of "
        "the ISSUE 16 A/B (default: both arms)",
    )
    ap.add_argument(
        "--flat",
        action="store_true",
        help="with --collective: run only the FLAT fan-out broadcast arm "
        "of the ISSUE 16 A/B (default: both arms)",
    )
    ap.add_argument(
        "--resize",
        action="store_true",
        help="with --collective: elastic-fleet arm (ISSUE 17) — IMPALA on "
        "the device-broadcast plane through a scripted 8→16→8 sampler "
        "resize (2→4→2 with --quick), recording broadcast-plane syncs vs "
        "host-sync fallbacks per phase; records RESIZEBENCH_r{N}.json",
    )
    ap.add_argument(
        "--transfer",
        action="store_true",
        help="transfer-plane A/B (ISSUE 10): cut-through broadcast at the "
        "r5 shape, pull striping 1-vs-2 replicas over a modeled per-source "
        "link, raw-vs-msgpack chunk framing, plus putget/shuffle dispatch "
        "regression guards; records TRANSFER_r{N}.json",
    )
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.smoke:
        results = {"host_cpus": os.cpu_count(), "mode": "smoke"}
        t0 = time.perf_counter()
        basic_suite(results, duration=0.3)
        results["smoke_wall_s"] = round(time.perf_counter() - t0, 1)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
        print(json.dumps(results))
        required = [
            "task_sync_per_s",
            "task_async100_per_s",
            "actor_call_sync_per_s",
            "actor_call_async100_per_s",
            "put_1mib_per_s",
            "putget_1mib_per_s",
        ]
        bad = [k for k in required if not results.get(k)]
        if bad:
            print(f"SMOKE FAILED: missing/zero metrics {bad}", file=sys.stderr)
            sys.exit(1)
        return

    if args.recorder_overhead:
        results = {"host_cpus": os.cpu_count(), "mode": "recorder_overhead"}
        t0 = time.perf_counter()
        # 150 pairs (~60s) is where the median converges on this box: the
        # noise is non-stationary (multi-second bursts), so short runs can
        # land anywhere in +-4% while long-horizon medians repeat within
        # ~0.4%.
        recorder_overhead_suite(
            results,
            block_tasks=128 if args.quick else 256,
            pairs=8 if args.quick else 150,
        )
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"OBSBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.hop_budget:
        results = {"host_cpus": os.cpu_count(), "mode": "hop_budget"}
        hop_budget_suite(results, duration=1.0 if args.quick else 3.0)
        compute_deltas_vs_prev(results, args.round)
        out = args.out or f"HOPBUDGET_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        return

    if args.device_objects:
        results = {"host_cpus": os.cpu_count(), "mode": "device_objects"}
        t0 = time.perf_counter()
        device_objects_suite(results, duration=0.4 if args.quick else 3.0)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        compute_deltas_vs_prev(
            results, args.round, prev_path=f"DEVBENCH_r{args.round - 1}.json"
        )
        out = args.out or f"DEVBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.dag:
        results = {"host_cpus": os.cpu_count(), "mode": "dag"}
        t0 = time.perf_counter()
        dag_suite(results, duration=0.5 if args.quick else 3.0)
        results["dag_wall_s"] = round(time.perf_counter() - t0, 1)
        compute_deltas_vs_prev(
            results, args.round, prev_path=f"DAGBENCH_r{args.round - 1}.json"
        )
        out = args.out or f"DAGBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps({k: v for k, v in results.items() if k != "dag_hop_budget"}))
        return

    if args.pipeline:
        results = {"host_cpus": os.cpu_count(), "mode": "pipeline"}
        t0 = time.perf_counter()
        pipeline_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        compute_deltas_vs_prev(
            results, args.round, prev_path=f"PIPEBENCH_r{args.round - 1}.json"
        )
        out = args.out or f"PIPEBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.serve:
        results = {"host_cpus": os.cpu_count(), "mode": "serve_llm"}
        t0 = time.perf_counter()
        serve_llm_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        compute_deltas_vs_prev(
            results, args.round, prev_path=f"SERVEBENCH_r{args.round - 1}.json"
        )
        out = args.out or f"SERVEBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.serve_ft:
        results = {"host_cpus": os.cpu_count(), "mode": "serve_ft"}
        t0 = time.perf_counter()
        serve_ft_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"FTBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.serve_disagg:
        results = {"host_cpus": os.cpu_count(), "mode": "serve_disagg"}
        t0 = time.perf_counter()
        serve_disagg_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"DISAGGBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.sim:
        results = {"host_cpus": os.cpu_count(), "mode": "sim"}
        t0 = time.perf_counter()
        sim_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"SIMBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.chaos:
        results = {"host_cpus": os.cpu_count(), "mode": "chaos"}
        t0 = time.perf_counter()
        chaos_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"CHAOSBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.collective and args.resize:
        results = {"host_cpus": os.cpu_count(), "mode": "resize"}
        t0 = time.perf_counter()
        resize_suite(results, quick=args.quick)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"RESIZEBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.collective:
        results = {"host_cpus": os.cpu_count(), "mode": "collective"}
        arms = tuple(
            t for t, on in (("tree", args.tree), ("flat", args.flat)) if on
        ) or ("tree", "flat")
        t0 = time.perf_counter()
        collective_suite(results, quick=args.quick, arms=arms)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        out = args.out or f"COLLBENCH_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    if args.transfer:
        results = {"host_cpus": os.cpu_count(), "mode": "transfer"}
        t0 = time.perf_counter()
        mib = 16 if args.quick else 100
        n_nodes = 4 if args.quick else 32
        # Guards run FIRST: they certify the untouched dispatch plane, so
        # they must not measure the worker-reaping/arena-cleanup tail of a
        # freshly-shut-down 32-node broadcast cluster.
        def shuffle_guard():
            # Best of 2 full shuffle passes (fresh cluster each — see the
            # putget_guard docstring for why windows never share a cluster).
            best: dict = {}
            for _ in range(1 if args.quick else 2):
                tmp: dict = {}
                shuffle_stress(
                    tmp, 50_000 if args.quick else 500_000, 8 if args.quick else 32
                )
                for k, v in tmp.items():
                    if k.endswith("_rows_per_s"):
                        best[k] = max(best.get(k, 0), v)
                    else:
                        best[k] = v
            results.update(best)

        for name, fn in [
            ("putget", lambda: putget_guard(results, 1.0 if args.quick else 3.0)),
            ("shuffle", shuffle_guard),
            ("transfer", lambda: transfer_suite(results, args.quick)),
            ("broadcast", lambda: broadcast_stress(results, mib, n_nodes)),
        ]:
            tt = time.perf_counter()
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                results[f"{name}_error"] = f"{type(e).__name__}: {e}"
            results[f"{name}_wall_s"] = round(time.perf_counter() - tt, 1)
        results["wall_s"] = round(time.perf_counter() - t0, 1)
        # Diff against r5: the last artifact carrying broadcast/shuffle/
        # putget numbers for this box (r6-r9 were hop/DAG/obs/devobj rounds).
        compute_deltas_vs_prev(results, args.round, prev_path="MICROBENCH_r5.json")
        out = args.out or f"TRANSFER_r{args.round}.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
        print(json.dumps(results))
        return

    # Reference envelope shapes (release/benchmarks/README.md:21-31), scaled
    # to this host in --quick mode: 1M queued / 10k args / 3k returns /
    # 10k-object get / 32 simulated nodes.
    duration = 1.0 if args.quick else 3.0
    n_tasks = 10_000 if args.quick else 1_000_000
    n_actors = 8 if args.quick else 64
    mib = 16 if args.quick else 100
    n_nodes = 4 if args.quick else 32
    n_args = 1_000 if args.quick else 10_000
    n_returns = 300 if args.quick else 3_000
    n_get = 1_000 if args.quick else 10_000

    results: dict = {"host_cpus": os.cpu_count()}
    for name, fn in [
        ("basic", lambda: basic_suite(results, duration)),
        ("hop_budget", lambda: hop_budget_suite(results, min(duration, 2.0))),
        ("queued", lambda: queued_tasks_stress(results, n_tasks)),
        ("actors", lambda: actor_swarm_stress(results, n_actors)),
        ("many_args", lambda: many_args_stress(results, n_args)),
        ("many_returns", lambda: many_returns_stress(results, n_returns)),
        ("get_many", lambda: get_many_objects_stress(results, n_get)),
        ("shuffle", lambda: shuffle_stress(
            results, 50_000 if args.quick else 500_000, 8 if args.quick else 32)),
        ("broadcast", lambda: broadcast_stress(results, mib, n_nodes)),
    ]:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            results[f"{name}_error"] = f"{type(e).__name__}: {e}"
        results[f"{name}_wall_s"] = round(time.perf_counter() - t0, 1)

    compute_deltas_vs_prev(results, args.round)
    out = args.out or f"MICROBENCH_r{args.round}.json"
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
