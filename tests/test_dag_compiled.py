"""Compiled execution graphs (dag/compiled.py + experimental/channel/).

Covers the acceptance surface of the subsystem: correct repeated dispatch
with ZERO raylet RPCs / ObjectRef allocations per iteration, the per-DAG
actor cache shared with classic execute(), application-error flow,
backpressure past max_buffered_results, read timeouts, teardown (channel
slots released back to the arena) and the chaos path — SIGKILL of a
mid-pipeline actor surfaces a typed error naming the dead stage instead of
hanging, and teardown still completes without leaking shm.
"""

import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode, MultiOutputNode
from ray_tpu.exceptions import ActorDiedError, GetTimeoutError, TaskError
from ray_tpu.experimental.channel import ChannelTimeoutError


@pytest.fixture(scope="module")
def compiled_cluster():
    """One cluster for the whole module: compiled-graph tests are isolated
    per-DAG (own actors, own channels, per-test before/after assertions),
    and a shared boot keeps this module's tier-1 wall-time small."""
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        yield
    finally:
        ray_tpu.shutdown()


@ray_tpu.remote
class Stage:
    def __init__(self, inc=1):
        self.inc = inc

    def work(self, x):
        return x + self.inc

    def mul(self, x):
        return x * 10

    def add(self, x, y):
        return x + y

    def boom(self, x):
        if x == 3:
            raise ValueError("x was 3")
        return x

    def slow(self, x):
        time.sleep(1.5)
        return x

    def pid(self):
        return os.getpid()


def _linear_dag(n_stages):
    stages = [Stage.bind() for _ in range(n_stages)]
    with InputNode() as inp:
        d = inp
        for s in stages:
            d = s.work.bind(d)
    return d, stages


def test_compiled_linear_pipeline_zero_control_plane(compiled_cluster):
    from ray_tpu._private import worker_context

    d, _ = _linear_dag(4)
    compiled = d.experimental_compile()
    try:
        assert compiled.execute(0).get() == 4  # warm the loop
        cw = worker_context.get_core_worker()
        raylet_seq0 = cw.raylet._seq
        owned0 = len(cw.owned)
        pending0 = len(cw.pending_tasks)
        for i in range(25):
            assert compiled.execute(i).get() == i + 4
        # The steady-state iteration touches neither the raylet nor the
        # ObjectRef/ownership plane — the whole point of compiling.
        assert cw.raylet._seq - raylet_seq0 == 0
        assert len(cw.owned) - owned0 == 0
        assert len(cw.pending_tasks) - pending0 == 0
    finally:
        compiled.teardown()


def test_compiled_hop_records_name_no_raylet_stage(compiled_cluster):
    """Under hop timing every compiled iteration leaves a ``path="compiled"``
    record stamped by the driver and by each stage, and by no raylet: the
    budget that ``summarize_hop_records`` builds from them has none on it."""
    from ray_tpu._private import worker_context
    from ray_tpu.util import tracing

    cw = worker_context.get_core_worker()
    d, _ = _linear_dag(3)
    compiled = d.experimental_compile()
    cw.cfg.hop_timing = True
    try:
        tracing.drain_hop_records()
        for i in range(5):
            assert compiled.execute(i).get() == i + 3
        budget = tracing.summarize_hop_records(tracing.drain_hop_records())["compiled"]
    finally:
        cw.cfg.hop_timing = False
        compiled.teardown()
    assert budget["count"] == 5, budget
    assert budget["raylet_rpcs_per_call"] == 0
    stages = budget["stages_us"]
    assert stages and not any("raylet" in s for s in stages), stages
    assert any("s2_" in s for s in stages), stages  # the last stage stamped too


def test_compiled_out_of_order_get_and_pipelining(compiled_cluster):
    d, _ = _linear_dag(2)
    compiled = d.experimental_compile()
    try:
        refs = [compiled.execute(i) for i in range(8)]
        # Consume newest-first: earlier results buffer driver-side.
        assert [r.get() for r in reversed(refs)] == [i + 2 for i in reversed(range(8))]
        # Repeated get returns the cached value.
        assert refs[0].get() == 2
    finally:
        compiled.teardown()


def test_compiled_multi_output_and_input_attributes(compiled_cluster):
    a, b = Stage.bind(), Stage.bind()
    with InputNode() as inp:
        dag = MultiOutputNode([a.work.bind(inp["x"]), b.mul.bind(inp["y"])])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute({"x": 1, "y": 2}).get() == [2, 20]
        assert compiled.execute({"x": 5, "y": 7}).get() == [6, 70]
    finally:
        compiled.teardown()


def test_compiled_fan_in_and_const_args(compiled_cluster):
    a, b, c = Stage.bind(), Stage.bind(), Stage.bind()
    with InputNode() as inp:
        left = a.work.bind(inp)
        right = b.mul.bind(inp)
        dag = c.add.bind(left, right)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(3).get() == (3 + 1) + (3 * 10)
    finally:
        compiled.teardown()


def test_compiled_validation_errors(compiled_cluster):
    @ray_tpu.remote
    def task(x):
        return x

    with InputNode() as inp:
        fn_dag = task.bind(inp)
    with pytest.raises(ValueError, match="actor-method nodes only"):
        fn_dag.experimental_compile()

    s = Stage.bind()
    no_input = s.work.bind(1)
    with pytest.raises(ValueError, match="InputNode"):
        no_input.experimental_compile()

    with InputNode() as inp:
        dangling_src = Stage.bind()
        used = s.work.bind(inp)
        dangling = dangling_src.work.bind(inp)  # produced, never consumed
        dag = MultiOutputNode([used])
    del dangling
    # (dangling node is unreachable from the root, so this compiles fine)
    dag.experimental_compile(max_buffered_results=2).teardown()


def test_compiled_application_error_flows_and_dag_survives(compiled_cluster):
    a, b = Stage.bind(), Stage.bind()
    with InputNode() as inp:
        dag = b.work.bind(a.boom.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(2).get() == 3
        with pytest.raises(TaskError, match="x was 3"):
            compiled.execute(3).get()
        # Per-iteration failure only: the pipeline keeps serving.
        assert compiled.execute(4).get() == 5
    finally:
        compiled.teardown()


def test_compiled_backpressure_blocks_producer(compiled_cluster):
    d, _ = _linear_dag(1)
    compiled = d.experimental_compile(max_buffered_results=2, submit_timeout_s=0.5)
    try:
        refs = [compiled.execute(i) for i in range(2)]
        time.sleep(0.3)  # drain the input ring into the output ring
        compiled.execute(2)
        with pytest.raises(ChannelTimeoutError, match="unconsumed"):
            for i in range(3, 8):  # must jam within num_slots extra writes
                compiled.execute(i)
        assert refs[0].get() == 1  # buffered results still retrievable
    finally:
        compiled.teardown()


def test_compiled_get_honors_timeout(compiled_cluster):
    s = Stage.bind()
    with InputNode() as inp:
        dag = s.slow.bind(inp)
    compiled = dag.experimental_compile()
    try:
        ref = compiled.execute(7)
        t0 = time.monotonic()
        with pytest.raises(GetTimeoutError):
            ref.get(timeout=0.2)
        assert time.monotonic() - t0 < 1.0
        assert ref.get() == 7  # late result still lands
    finally:
        compiled.teardown()


def test_compiled_multi_output_get_timeout_keeps_iterations_paired(compiled_cluster):
    """A get(timeout=) that expires after consuming SOME output channels of
    an iteration must not skew pairing: the partially-drained envelopes
    stage, and the retry resumes with the same iteration."""
    fast, slow = Stage.bind(), Stage.bind()
    with InputNode() as inp:
        dag = MultiOutputNode([fast.work.bind(inp), slow.slow.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        ref = compiled.execute(1)
        with pytest.raises(GetTimeoutError):
            ref.get(timeout=0.3)  # fast output consumed, slow still pending
        assert ref.get() == [2, 1]
        assert compiled.execute(5).get() == [6, 5]  # pairing intact
    finally:
        compiled.teardown()


def test_compiled_abandoned_results_raise_instead_of_leaking(compiled_cluster):
    """Skipping refs cannot grow the driver-side result buffer without
    bound: draining past max_buffered_results unconsumed results raises."""
    d, _ = _linear_dag(1)
    compiled = d.experimental_compile(max_buffered_results=2)
    try:
        refs = [compiled.execute(i) for i in range(3)]
        with pytest.raises(ValueError, match="buffered"):
            refs[2].get(timeout=10)
        # Nothing was lost: consuming in order recovers every result.
        assert [refs[i].get(timeout=10) for i in range(3)] == [1, 2, 3]
    finally:
        compiled.teardown()


def test_compiled_execute_after_teardown_raises(compiled_cluster):
    d, _ = _linear_dag(1)
    compiled = d.experimental_compile()
    assert compiled.execute(1).get() == 2
    compiled.teardown()
    compiled.teardown()  # idempotent
    with pytest.raises(ValueError, match="torn down"):
        compiled.execute(2)


def test_compiled_actor_death_chaos(compiled_cluster):
    """SIGKILL a mid-pipeline actor during compiled execution: get() raises
    a typed error naming the dead stage, teardown() completes, and the
    channel slots return to the arena (no leaked shm)."""
    from ray_tpu._private import worker_context

    cw = worker_context.get_core_worker()
    stages = [Stage.bind() for _ in range(3)]
    pids = [ray_tpu.get(s.resolve_actor_handle().pid.remote()) for s in stages]
    with InputNode() as inp:
        d = inp
        for s in stages:
            d = s.work.bind(d)
    store0 = cw.raylet.call("get_state")["store"]
    compiled = d.experimental_compile()
    assert compiled.execute(0).get() == 3
    assert cw.raylet.call("get_state")["store"]["num_channels"] > 0

    os.kill(pids[1], signal.SIGKILL)
    ref = compiled.execute(1)
    with pytest.raises(ActorDiedError, match="1:work"):
        ref.get(timeout=30)
    with pytest.raises(ActorDiedError):
        compiled.execute(2)

    t0 = time.monotonic()
    compiled.teardown()
    # no close is sent to the dead stage's address (each ran four connects of 10 s out)
    assert time.monotonic() - t0 < 10.0
    store1 = cw.raylet.call("get_state")["store"]
    assert store1["num_channels"] == store0["num_channels"]
    assert store1["used"] <= store0["used"]


def test_classic_calls_still_served_while_compiled(compiled_cluster):
    """The resident loop runs on its own thread: an actor bound into a
    compiled graph still answers classic method calls (and classic
    execute() of the same DAG) instead of queuing behind the loop forever."""
    d, stages = _linear_dag(2)
    compiled = d.experimental_compile()
    try:
        assert compiled.execute(1).get() == 3
        handle = stages[0].resolve_actor_handle()
        assert ray_tpu.get(handle.work.remote(10), timeout=20) == 11
        assert ray_tpu.get(d.execute(1), timeout=30) == 3  # classic walk
        assert compiled.execute(2).get() == 4  # compiled path unaffected
    finally:
        compiled.teardown()


def test_compiled_oversize_payload_side_channel(compiled_cluster):
    """Envelopes larger than a ring slot ride the chunked side-channel
    (marker slot + acked channel_data chunks) and still arrive in order."""
    np = pytest.importorskip("numpy")

    @ray_tpu.remote
    class Big:
        def double(self, arr):
            return arr * 2

    b = Big.bind()
    with InputNode() as inp:
        dag = b.double.bind(inp)
    # 8 KiB slots vs ~1 MiB payloads: every hop goes side-channel.
    compiled = dag.experimental_compile(slot_size_bytes=8 * 1024)
    try:
        arr = np.arange(256 * 1024, dtype=np.int32)
        for i in range(3):
            out = compiled.execute(arr + i).get()
            assert out.dtype == np.int32 and out[1] == (1 + i) * 2
        assert compiled.execute(np.int32(21)).get() == 42  # small again
    finally:
        compiled.teardown()


def test_channel_remote_mode_fallback(compiled_cluster):
    """Cross-node (no shared arena) channels: every envelope rides the
    chunked RPC path with channel_query backpressure. Exercised directly
    with both endpoints in this process and a remote-only descriptor."""
    from ray_tpu._private import worker_context
    from ray_tpu.experimental.channel import (
        KIND_VALUE,
        ChannelReader,
        ChannelTimeoutError as CTE,
        ChannelWriter,
        make_descriptor,
    )
    from ray_tpu._private import serialization

    cw = worker_context.get_core_worker()
    desc = make_descriptor(
        "rm" * 12, arena=None, offset=0, num_slots=2, slot_size=8 * 1024,
        reader_addr=cw.address, label="remote-test",
    )
    writer = ChannelWriter(desc, cw)
    reader = ChannelReader(desc, cw)
    assert not writer.shm and not reader.shm
    kinds_vals = []
    for i in range(3):
        writer.write(KIND_VALUE, serialization.serialize(i * 7).to_bytes())
        kind, data, _hop = reader.read(timeout=5)
        kinds_vals.append((kind, serialization.deserialize(data)))
    assert kinds_vals == [(KIND_VALUE, 0), (KIND_VALUE, 7), (KIND_VALUE, 14)]
    # Backpressure: 2 unconsumed envelopes fill the remote queue bound.
    writer.write(KIND_VALUE, serialization.serialize(1).to_bytes())
    writer.write(KIND_VALUE, serialization.serialize(2).to_bytes())
    with pytest.raises(CTE):
        writer.write(KIND_VALUE, serialization.serialize(3).to_bytes(), timeout=0.5)
    cw.channels.drop([desc["cid"]])


def test_classic_execute_reuses_actor_gang(compiled_cluster):
    """Satellite: classic dag.execute() on ClassNode graphs reuses the
    per-DAG actor cache instead of spawning fresh actors per call."""

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.v = 0

        def add(self, x):
            self.v += x
            return self.v

        def pid(self):
            return os.getpid()

    with InputNode() as inp:
        counter = Counter.bind()
        dag = counter.add.bind(inp)
    assert ray_tpu.get(dag.execute(5)) == 5
    # Same actor: state accumulates and the pid is stable across executes.
    assert ray_tpu.get(dag.execute(5)) == 10
    pid_dag = counter.pid.bind()
    assert ray_tpu.get(pid_dag.execute()) == ray_tpu.get(pid_dag.execute())


def test_compile_rejects_double_binding(compiled_cluster):
    from ray_tpu._private import worker_context

    cw = worker_context.get_core_worker()
    d, stages = _linear_dag(1)
    compiled = d.experimental_compile()
    try:
        channels_live = cw.raylet.call("get_state")["store"]["num_channels"]
        with InputNode() as inp:
            other = stages[0].mul.bind(inp)
        with pytest.raises(ValueError, match="already participates"):
            other.experimental_compile()
        # The failed compile released every channel it had allocated.
        assert (
            cw.raylet.call("get_state")["store"]["num_channels"] == channels_live
        )
    finally:
        compiled.teardown()
    # After teardown the actor is free to join a new compiled graph.
    compiled2 = other.experimental_compile()
    try:
        assert compiled2.execute(3).get() == 30
    finally:
        compiled2.teardown()
