"""Chaos matrix harness — N workloads x M seeded fault cells.

The library behind tests/test_chaos_matrix.py: each CELL runs one small workload under one seeded fault plan injected at
the RPC frame seam (chaos.py) and asserts the availability contract:

(a) the workload COMPLETES, or raises/returns the documented *typed*
    failure naming the failed component (never a raw 2-minute
    TimeoutError);
(b) recovery lands within the cell's wall-clock BUDGET;
(c) nothing LEAKS: per-node store objects, channel count, and the
    driver's device-object residents return to their pre-cell baseline
    (the LLM workload additionally asserts its KV-block free list drains
    back to full inside the workload itself).

Fault plans are deterministic: counted rules (``after``/``every``/
``times``) plus the plan's seeded RNG for jitter — same seed over the same
frame stream, same injection sequence (pinned by
test_chaos_plane.test_same_seed_same_injection_sequence); each cell's
actual sequence is returned in the cell result for reproduction.

Workloads (each a few seconds unfaulted):
  tasks      task retry loop (12 remote tasks, max_retries)
  actors     actor call fan-out (2 actors x 8 calls)
  pull       2-replica striped pull onto a third node
  broadcast  cut-through relay broadcast to 3 nodes
  devobj     device-object handoff driver -> worker task
  pipeline   compiled-DAG iterations (shm channels + doorbells)
  llm        one LLM-engine streaming request (streaming generator task)

Faults: drop, delay, dup, reset, partition (a victim node severed via
Cluster.partition_node and healed mid-workload by a timer), and kill —
the CRASH column: a seeded plan pushed into the workload's WORKER
processes makes one SIGKILL itself at the Nth matching frame (the raylets
share the test process and cannot be killed; for the two raylet-plane
workloads with no worker in the data path — pull, broadcast — the cell
SIGKILLs a bystander worker via Cluster.kill_role instead, asserting
crash NON-interference). Kill evidence comes from the flight recorder:
the dying side stamps ``chaos_kill`` into its mmap ring first, and the
cell harvests those events from the node postmortem into the injection
log, since the killed process's in-memory plan.log dies with it.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

FAULTS = ("drop", "delay", "dup", "reset", "partition", "kill")
WORKLOAD_NAMES = ("tasks", "actors", "pull", "broadcast", "devobj", "pipeline", "llm")

# Methods whose frames each workload's hot path rides (drop/reset target
# these so the injection provably lands on the workload, not bystander
# heartbeats). delay/dup cells go wide (method=None) on purpose.
_METHODS = {
    "tasks": ["submit_task", "lease_exec", "push_task", "task_done",
              "tasks_done", "request_worker_lease"],
    "actors": ["actor_call", "submit_task", "task_done", "tasks_done"],
    "pull": ["fetch_object_info", "fetch_object_chunk", "raw_chunk"],
    "broadcast": ["push_begin", "push_chunk", "raw_chunk", "push_commit"],
    "devobj": ["devobj_pull", "p2p_data", "get_inline", "lease_exec",
               "tasks_done"],
    "pipeline": ["channel_doorbell", "channel_data", "actor_call",
                 "channel_create"],
    "llm": ["stream_item", "lease_exec", "tasks_done", "push_task"],
}

# Crash column: per-workload kill rules for the WORKER-side frames the
# workload rides (the plan is pushed into worker processes; a raylet-plane
# frame can never match there). `after` picks the Nth matching frame —
# counted firing, no RNG — so the kill point is deterministic per seed by
# construction. pull/broadcast have no worker in their data path and use
# the kill_role bystander kill instead.
_KILL_RULES = {
    "tasks": {"method": ["task_done", "tasks_done"], "after": 1},
    "actors": {"method": ["actor_call"], "side": "resp", "after": 2},
    "devobj": {"method": ["task_done", "tasks_done"], "after": 0},
    "pipeline": {"method": ["channel_doorbell", "channel_data", "actor_call"],
                 "after": 2},
    "llm": {"method": ["stream_item"], "after": 2},
}

# Typed failure contract (a): a cell may surface a RayTpuError subclass
# that NAMES a component (ActorDiedError names the actor, TaskError the
# task, DeviceObjectLostError the holder, ...). Timeouts are NOT typed —
# a raw TimeoutError (or GetTimeoutError, which merely restates the
# caller's patience) is exactly the 2-minute-silence failure mode the
# matrix exists to ban.
def _is_typed(e: BaseException) -> bool:
    import ray_tpu.exceptions as ex

    return isinstance(e, ex.RayTpuError) and not isinstance(e, TimeoutError)


class CellResult:
    def __init__(self, workload, fault, seed):
        self.workload = workload
        self.fault = fault
        self.seed = seed
        self.ok = False
        self.error: str | None = None
        self.typed = False
        self.elapsed = 0.0
        self.injected = 0
        self.injection_log: list = []
        self.leaks: dict = {}

    def summary(self) -> dict:
        return {
            "cell": f"{self.workload}x{self.fault}",
            "seed": self.seed, "ok": self.ok, "typed": self.typed,
            "error": self.error, "elapsed_s": round(self.elapsed, 2),
            "injected": self.injected, "leaks": self.leaks,
        }


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------


def fault_plan(fault: str, workload: str) -> dict | None:
    """The seeded plan spec for one cell. Bounded (``times``) so every cell
    can complete; `partition` returns None — it is driven by
    partition_node + a heal timer instead of frame rules; `kill` returns
    the worker-push plan (or None for the kill_role workloads) — run_cell
    installs it in the WORKER processes, never this one."""
    methods = _METHODS[workload]
    if fault == "kill":
        rule = _KILL_RULES.get(workload)
        if rule is None:
            return None  # pull/broadcast: kill_role bystander crash
        return {"rules": [dict(rule, kind="kill", times=1)]}
    if fault == "drop":
        return {"rules": [{"kind": "drop", "method": methods, "every": 2, "times": 4}]}
    if fault == "delay":
        return {"rules": [{"kind": "delay", "delay_ms": [10, 60], "every": 3, "times": 24}]}
    if fault == "dup":
        return {"rules": [{"kind": "dup", "every": 2, "times": 24}]}
    if fault == "reset":
        return {"rules": [
            # Tear one frame mid-header and one mid-payload.
            {"kind": "reset", "method": methods, "reset_at": 3, "times": 1},
            {"kind": "reset", "method": methods, "reset_at": 40, "after": 4, "times": 1},
        ]}
    if fault == "partition":
        return None
    raise ValueError(fault)


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


def _wl_tasks(ctx):
    import ray_tpu

    @ray_tpu.remote(max_retries=4)
    def double(i):
        return i * 2

    refs = [double.remote(i) for i in range(12)]
    out = ray_tpu.get(refs, timeout=ctx["budget_s"])
    assert out == [i * 2 for i in range(12)], out


def _wl_actors(ctx):
    import ray_tpu

    @ray_tpu.remote(max_restarts=1)
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self, k):
            self.n += k
            return self.n

    actors = [Counter.remote() for _ in range(2)]
    try:
        refs = [a.bump.remote(1) for a in actors for _ in range(8)]
        out = ray_tpu.get(refs, timeout=ctx["budget_s"])
        assert sorted(out) == sorted(list(range(1, 9)) * 2), out
    finally:
        for a in actors:
            ray_tpu.kill(a)


def _oid(tag: str) -> str:
    return tag.encode().hex().ljust(56, "0")[:56]


def _seal_raw(io, node, oid, data):
    offset = io.run(node.store.create(oid, len(data)))
    assert offset is not None
    node.arena.write(offset, data)
    node.store.seal(oid)
    io.run(node.gcs.acall(
        "add_object_location", {"object_id": oid, "node_id": node.node_id}
    ))


def _free_all(nodes, oid):
    for n in nodes:
        try:
            n.store.delete(oid)
        except Exception:
            pass


def _prep_pull(ctx):
    """Pre-fault setup: seal the object on nodes[0] and replicate it onto
    nodes[1], so the faulted phase is a clean 2-replica striped pull (and a
    partition of nodes[1] — a SOURCE — exercises failover, not setup)."""
    io, nodes = ctx["io"], ctx["nodes"]
    data = np.random.default_rng(ctx["seed"]).integers(
        0, 255, 6 * 1024 * 1024, dtype=np.uint8
    ).tobytes()
    oid = _oid(f"chaospull{ctx['seed']}")
    ctx["prep"] = {"oid": oid, "data": data}
    _seal_raw(io, nodes[0], oid, data)
    io.run(nodes[1].pull_manager.pull(oid, timeout=60), timeout=60)


def _wl_pull(ctx):
    """Chunked pull with 2 source replicas onto a third node: chunk faults
    must fail over / retry, never corrupt (bytes compared)."""
    io, nodes = ctx["io"], ctx["nodes"]
    oid, data = ctx["prep"]["oid"], ctx["prep"]["data"]
    budget = ctx["budget_s"] * 0.9
    try:
        io.run(nodes[2].pull_manager.pull(oid, timeout=budget), timeout=budget)
        offset, size = io.run(nodes[2].store.get(oid))
        try:
            got = bytes(nodes[2].arena.read(offset, size))
        finally:
            nodes[2].store.release(oid)
        assert got == data, "pulled bytes corrupt"
    finally:
        _free_all(nodes, oid)


def _wl_broadcast(ctx):
    """Cut-through relay broadcast to every other node; a not-ok outcome
    must NAME the failed nodes (the documented typed failure shape)."""
    io, nodes = ctx["io"], ctx["nodes"]
    data = np.random.default_rng(ctx["seed"] + 1).integers(
        0, 255, 5 * 1024 * 1024, dtype=np.uint8
    ).tobytes()
    oid = _oid(f"chaosbcast{ctx['seed']}")
    try:
        _seal_raw(io, nodes[0], oid, data)
        resp = io.run(
            nodes[0].rpc_broadcast_object({
                "object_id": oid,
                "targets": [
                    {"node_id": n.node_id, "address": list(n.address)}
                    for n in nodes[1:]
                ],
                "timeout": ctx["budget_s"] * 0.8,
            }),
            timeout=ctx["budget_s"] * 0.9,
        )
        if not resp.get("ok"):
            # Documented failure shape: failed subtree NODES are named.
            known = {n.node_id for n in nodes}
            assert resp.get("failed"), resp
            assert set(resp["failed"]) <= known, resp
            return
        for n in nodes[1:]:
            offset, size = io.run(n.store.get(oid))
            try:
                assert bytes(n.arena.read(offset, size)) == data
            finally:
                n.store.release(oid)
    finally:
        _free_all(nodes, oid)


def _wl_devobj(ctx):
    """Device-object handoff: driver holds a jax.Array, a worker task
    resolves it through devobj_pull (inline/host fallback on this CPU
    testbed) — loss must surface as DeviceObjectLostError naming the
    holder, never hang."""
    import jax.numpy as jnp

    import ray_tpu

    @ray_tpu.remote(max_retries=2)
    def consume(arr):
        return float(np.asarray(arr).sum())

    ref = ray_tpu.put(jnp.ones(512, jnp.float32), tensor_transport="collective")
    try:
        out = ray_tpu.get(consume.remote(ref), timeout=ctx["budget_s"])
        assert out == 512.0, out
    finally:
        del ref


def _wl_pipeline(ctx):
    """Compiled-DAG iterations over shm channels: doorbell/side-channel
    faults must be healed by the poll backstop; teardown must reclaim every
    channel even after faults."""
    import ray_tpu
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Stage:
        def work(self, x):
            return x + 1

    stages = [Stage.bind() for _ in range(2)]
    compiled = None
    try:
        with InputNode() as inp:
            d = inp
            for s in stages:
                d = s.work.bind(d)
        compiled = d.experimental_compile()
        for i in range(6):
            assert compiled.execute(i).get(timeout=ctx["budget_s"] / 3) == i + 2
    finally:
        if compiled is not None:
            compiled.teardown()


def _wl_llm(ctx):
    """One LLM-engine streaming request: tokens stream back over the wire
    (streaming-generator stream_item frames) while the engine runs in a
    worker; the KV-block free list must drain back to full."""
    import ray_tpu

    # max_retries exceeds the cluster's warm-worker count: a kill-cell
    # retry can land on ANOTHER armed worker (its own kill rule unfired —
    # only the streaming worker emits stream_item) and die again; the
    # attempt budget must outlast every armed worker once.
    @ray_tpu.remote(num_returns="streaming", max_retries=5)
    def llm_stream(n_tokens):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import TransformerConfig, init_params
        from ray_tpu.serve.llm import LLMEngine

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=48, max_seq_len=48, dtype=jnp.float32, remat=False,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = LLMEngine(params, cfg, num_slots=1, block_size=4,
                        max_model_len=32, prefill_chunk=4)
        try:
            req = eng.submit([1, 2, 3, 4], max_new_tokens=n_tokens)
            for tok in req:
                yield int(tok)
            s = eng.stats()
            # KV free-list back to baseline INSIDE the engine process.
            assert s["free_blocks"] + s.get("cached_blocks", 0) == s["num_blocks"], s
        finally:
            eng.shutdown()

    gen = llm_stream.remote(6)
    toks = [ray_tpu.get(r, timeout=ctx["budget_s"]) for r in gen]
    assert len(toks) == 6 and all(isinstance(t, int) for t in toks), toks


WORKLOADS = {
    "tasks": _wl_tasks,
    "actors": _wl_actors,
    "pull": _wl_pull,
    "broadcast": _wl_broadcast,
    "devobj": _wl_devobj,
    "pipeline": _wl_pipeline,
    "llm": _wl_llm,
}

# Pre-fault setup phases (run OUTSIDE the fault window): the faulted phase
# must exercise the workload's recovery path, not its scaffolding.
PREPARES = {"pull": _prep_pull}


# ---------------------------------------------------------------------------
# leak checks
# ---------------------------------------------------------------------------


def leak_baseline(ctx) -> dict:
    from ray_tpu.experimental.device_object.manager import active_manager

    gc.collect()
    mgr = active_manager()
    return {
        "store_objects": [n.store.usage()["num_objects"] for n in ctx["nodes"]],
        "channels": [n.store.usage()["num_channels"] for n in ctx["nodes"]],
        "devobj_resident": 0 if mgr is None else mgr.usage()["resident_count"],
    }


def leak_check(ctx, baseline: dict, settle_s: float = 20.0) -> dict:
    """Wait (frees are async) for every counter to return to baseline;
    returns {} when clean, else the surviving diffs."""
    deadline = time.monotonic() + settle_s
    diff: dict = {}
    while time.monotonic() < deadline:
        gc.collect()
        cur = leak_baseline(ctx)
        diff = {
            k: {"before": baseline[k], "after": cur[k]}
            for k in baseline
            if cur[k] != baseline[k]
        }
        if not diff:
            return {}
        time.sleep(0.25)
    return diff


# ---------------------------------------------------------------------------
# kill-cell plumbing (crash column)
# ---------------------------------------------------------------------------


def _live_worker_clients(ctx):
    out = []
    for n in ctx["nodes"]:
        for w in n.workers.values():
            if w.client is not None and w.state not in ("starting", "dead"):
                out.append(w)
    return out


def _push_plan_to_workers(ctx, plan, seed) -> list:
    """Install a plan in every live WORKER process (the kill victims); the
    driver/raylet process never sees it. Returns the workers reached."""
    io, pushed = ctx["io"], []
    for w in _live_worker_clients(ctx):
        try:
            io.run(
                w.client.acall(
                    "chaos_set_plan", {"plan": plan, "seed": seed},
                    timeout=5, retries=0,
                ),
                timeout=6,
            )
            pushed.append(w)
        except Exception:
            pass  # already-dying workers are, well, chaos
    return pushed


def _collect_kill_events(ctx, since_wall: float) -> list:
    """The killed process's plan.log died with it; its chaos_kill flight
    event survived in the mmap ring. Harvest the node postmortem (raylets
    share one session flight dir) into the cell's injection log."""
    try:
        resp = ctx["io"].run(ctx["nodes"][0].rpc_debug_dump({}), timeout=15)
    except Exception:
        return []
    out = []
    for proc in resp.get("processes", []):
        for ev in proc.get("events", []):
            if ev.get("type") == "chaos_kill" and ev.get("ts", 0) >= since_wall - 2.0:
                out.append(f"kill:{ev.get('detail', '')}")
    return out


# ---------------------------------------------------------------------------
# the cell runner
# ---------------------------------------------------------------------------


def run_cell(ctx, workload: str, fault: str, seed: int,
             budget_s: float = 60.0) -> CellResult:
    """Run one (workload, fault) cell under its seeded plan. Asserts
    nothing itself — returns a CellResult the caller asserts on (the test
    layer and the bench artifact share this)."""
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import CHAOS_STATS

    res = CellResult(workload, fault, seed)
    ctx = dict(ctx, budget_s=budget_s, seed=seed)
    baseline = leak_baseline(ctx)
    prep = PREPARES.get(workload)
    if prep is not None:
        prep(ctx)  # pre-fault: the cell measures recovery, not setup
    injected_before = CHAOS_STATS.injected
    heal_timer = None
    plan = None
    pushed_kill: list = []
    t_wall0 = time.time()
    t0 = time.monotonic()
    try:
        if fault == "kill":
            spec = fault_plan("kill", workload)
            if spec is None:
                # Raylet-plane workload: SIGKILL a bystander worker process
                # (crash NON-interference — the data path must not notice).
                # Earlier kill cells may have eaten every warm worker, so
                # spawn one to sacrifice if none is live.
                if not ctx["cluster"]._live_workers():
                    import ray_tpu

                    @ray_tpu.remote
                    def _sacrifice():
                        return 1

                    assert ray_tpu.get(_sacrifice.remote(), timeout=60) == 1
                ctx["cluster"].kill_role("worker")
            else:
                # Arm AFTER re-warming the worker pool: a prior cell may
                # have consumed workers (the actors workload kills its
                # actor workers), and a workload task landing in a FRESH
                # worker spawned after the push would run unarmed — the
                # cell would pass with zero injections, which the subset
                # rightly rejects.
                # One warm task PINNED to each node: unpinned, the tasks are
                # so short that one node's worker can serve them all, and the
                # workload then lands on a node whose worker was spawned for
                # it, unarmed.
                import ray_tpu
                from ray_tpu.util.scheduling_strategies import (
                    NodeAffinitySchedulingStrategy,
                )

                @ray_tpu.remote
                def _warm_pool():
                    return 1

                ray_tpu.get(
                    [
                        _warm_pool.options(
                            scheduling_strategy=NodeAffinitySchedulingStrategy(n.node_id)
                        ).remote()
                        for n in ctx["nodes"]
                    ],
                    timeout=60,
                )
                pushed_kill = _push_plan_to_workers(ctx, spec, seed)
        elif fault == "partition":
            # Sever a victim raylet (never nodes[0]: the driver's head node
            # going dark is driver death, a different chaos class), heal
            # mid-workload. The window stays under node_death_timeout_s so
            # the cell exercises transport recovery; the full
            # die-and-rejoin path has its own dedicated test.
            victim = ctx["nodes"][1]
            ctx["cluster"].partition_node(victim)
            heal_timer = threading.Timer(
                ctx.get("partition_s", 1.5),
                lambda: ctx["cluster"].heal_node(victim),
            )
            heal_timer.daemon = True
            heal_timer.start()
        else:
            plan = chaos.install(fault_plan(fault, workload), seed=seed)
        WORKLOADS[workload](ctx)
        res.ok = True
    except Exception as e:  # noqa: BLE001 — the cell judges the class
        res.error = f"{type(e).__name__}: {e}"
        res.typed = _is_typed(e)
    finally:
        if heal_timer is not None:
            heal_timer.cancel()
            ctx["cluster"].heal_node(ctx["nodes"][1])
        if plan is not None:
            res.injection_log = list(plan.log)
        if pushed_kill:
            # Disarm survivors (the fired victim is dead and unreachable).
            for w in pushed_kill:
                try:
                    ctx["io"].run(
                        w.client.acall(
                            "chaos_set_plan", {"plan": None}, timeout=5, retries=0
                        ),
                        timeout=6,
                    )
                except Exception:
                    pass
        chaos.clear()
    res.elapsed = time.monotonic() - t0
    res.injected = CHAOS_STATS.injected - injected_before
    if fault == "kill":
        # Kill evidence lives in the flight postmortem, not this process's
        # counters (the victim's plan died with it; kill_role stamps the
        # driver ring, plan-driven kills stamp the victim's).
        res.injection_log = _collect_kill_events(ctx, t_wall0)
        res.injected = max(res.injected, len(res.injection_log))
    res.leaks = leak_check(ctx, baseline)
    return res


def assert_cell(res: CellResult, budget_s: float):
    """Contract (a)+(b)+(c) for one cell."""
    assert res.ok or res.typed, (
        f"cell {res.workload}x{res.fault} failed UNTYPED: {res.error} "
        f"(injections: {res.injection_log})"
    )
    assert res.elapsed <= budget_s, (
        f"cell {res.workload}x{res.fault} blew its recovery budget: "
        f"{res.elapsed:.1f}s > {budget_s}s"
    )
    assert not res.leaks, (
        f"cell {res.workload}x{res.fault} leaked: {res.leaks}"
    )


# ---------------------------------------------------------------------------
# Sim-scale SLO cells (ISSUE 19): the same seeded-chaos philosophy at
# 100-1000 raylet shells via _private/simnode. A cell builds its own
# SimCluster, drives closed-loop SimTraffic while injecting its fault, and
# returns an SLO scorecard: p99 placement latency, dropped streams, and the
# typed-failure contract (never a raw TimeoutError). Everything is seeded —
# reproduce a scorecard from its seed (see CHAOS.md).
# ---------------------------------------------------------------------------

SIM_CELLS = ("node_kill", "partition_heal_storm", "rolling_update")


class SimCellResult:
    def __init__(self, cell, seed, num_nodes):
        self.cell = cell
        self.seed = seed
        self.num_nodes = num_nodes
        self.ok = False
        self.error: str | None = None
        self.elapsed = 0.0
        self.slo: dict = {}

    def summary(self) -> dict:
        return {
            "cell": self.cell, "seed": self.seed, "nodes": self.num_nodes,
            "ok": self.ok, "error": self.error,
            "elapsed_s": round(self.elapsed, 2), "slo": self.slo,
        }


def _sim_config(heartbeat_s=0.2, death_timeout_s=1.5, **extra) -> dict:
    cfg = {
        "heartbeat_interval_s": heartbeat_s,
        "node_death_timeout_s": death_timeout_s,
        # Fast, deterministic-ish rejoin at test cadence.
        "rejoin_backoff_base_s": 0.02,
        "rejoin_backoff_max_s": 0.5,
    }
    cfg.update(extra)
    return cfg


def _untyped(failures: dict) -> list:
    """Failure-type names that violate the typed contract. SimTraffic
    converts every loss to a RayTpuError subclass; anything resembling a
    bare timeout here is a bug."""
    return [
        name for name in failures
        if "Timeout" in name and name != "GetTimeoutError"
        or name in ("TimeoutError", "CancelledError", "Exception")
    ]


def run_sim_node_kill(num_nodes=96, seed=11, kills=8, duration_s=5.0,
                      p99_budget_ms=2000.0) -> SimCellResult:
    """Seeded node-kill under diurnal traffic: kill `kills` seeded-chosen
    non-entry shells mid-run. SLO: traffic keeps completing, every failure
    typed, post-recovery p99 placement under budget."""
    import random as _random

    from ray_tpu._private.simnode import SimCluster, SimTraffic

    res = SimCellResult("node_kill", seed, num_nodes)
    t0 = time.time()
    c = SimCluster(num_nodes, resources_per_node={"CPU": 4},
                   _system_config=_sim_config(), seed=seed)
    try:
        c.start()
        c.wait_for_view(timeout=60)
        rng = _random.Random(seed)
        victims = rng.sample(
            [n for n in c.nodes if n not in c.entry_nodes], kills
        )
        traffic = SimTraffic(c, users=16, pattern="diurnal", think_s=0.01,
                             sim_ms=5.0, task_timeout_s=3.0, seed=seed)
        killed = []

        def _assassin():
            time.sleep(duration_s * 0.3)
            for v in victims:
                c.kill_node(v)
                killed.append(v.node_id)

        th = threading.Thread(target=_assassin, daemon=True)
        th.start()
        stats = traffic.run(duration_s)
        th.join(timeout=30)
        untyped = _untyped(stats["failures"])
        # Post-kill placements only: the SLO judges recovery, not the
        # pre-fault warmup.
        p99_ms = 0.0
        lat = c.placement_latencies()
        if lat:
            tail = sorted(lat[len(lat) // 2:])
            p99_ms = tail[min(len(tail) - 1, int(0.99 * len(tail)))] * 1000.0
        res.slo = {
            "completed": stats["completed"],
            "submitted": stats["submitted"],
            "failures": stats["failures"],
            "resubmits": stats["resubmits"],
            "killed": len(killed),
            "untyped": untyped,
            "p99_placement_ms": round(p99_ms, 2),
            "p99_budget_ms": p99_budget_ms,
        }
        res.ok = (
            stats["completed"] > 0
            and not untyped
            and len(killed) == kills
            and p99_ms <= p99_budget_ms
        )
        if not res.ok and res.error is None:
            res.error = f"slo violation: {res.slo}"
    except Exception as e:  # noqa: BLE001 — scorecard judges
        res.error = f"{type(e).__name__}: {e}"
    finally:
        c.shutdown()
    res.elapsed = time.time() - t0
    return res


def run_sim_partition_heal_storm(num_nodes=96, seed=23, victims=24,
                                 duration_s=6.0) -> SimCellResult:
    """Partition a quarter of the fleet past the death timeout, then heal
    ALL at once: the rejoin storm the jittered backoff exists to flatten.
    SLO: every victim back ALIVE within budget, node-row count unchanged
    (no duplicate registrations), traffic failures all typed."""
    import random as _random

    from ray_tpu._private.simnode import SimCluster, SimTraffic

    res = SimCellResult("partition_heal_storm", seed, num_nodes)
    t0 = time.time()
    c = SimCluster(num_nodes, resources_per_node={"CPU": 4},
                   _system_config=_sim_config(), seed=seed)
    try:
        c.start()
        c.wait_for_view(timeout=60)
        rows_before = len(c.gcs.nodes)
        rng = _random.Random(seed)
        chosen = rng.sample(
            [n for n in c.nodes if n not in c.entry_nodes], victims
        )
        traffic = SimTraffic(c, users=12, pattern="bursty", think_s=0.01,
                             sim_ms=5.0, task_timeout_s=3.0, seed=seed)

        def _storm():
            time.sleep(duration_s * 0.2)
            for v in chosen:
                c.partition_node(v, True)
            # Hold past the death timeout so the GCS writes them off...
            time.sleep(2.5)
            # ...then heal EVERYONE in the same instant.
            for v in chosen:
                c.partition_node(v, False)

        th = threading.Thread(target=_storm, daemon=True)
        th.start()
        stats = traffic.run(duration_s)
        th.join(timeout=30)
        deadline = time.time() + 20
        back = 0
        while time.time() < deadline:
            back = sum(
                1 for v in chosen
                if c.gcs.nodes.get(v.node_id, {}).get("state") == "ALIVE"
            )
            if back == len(chosen):
                break
            time.sleep(0.1)
        untyped = _untyped(stats["failures"])
        res.slo = {
            "completed": stats["completed"],
            "failures": stats["failures"],
            "untyped": untyped,
            "victims": len(chosen),
            "rejoined": back,
            "node_rows_before": rows_before,
            "node_rows_after": len(c.gcs.nodes),
        }
        res.ok = (
            back == len(chosen)
            and len(c.gcs.nodes) == rows_before  # rejoin != re-register anew
            and not untyped
            and stats["completed"] > 0
        )
        if not res.ok and res.error is None:
            res.error = f"slo violation: {res.slo}"
    except Exception as e:  # noqa: BLE001
        res.error = f"{type(e).__name__}: {e}"
    finally:
        c.shutdown()
    res.elapsed = time.time() - t0
    return res


def run_sim_rolling_update(num_nodes=64, seed=37, streams=12,
                           chunks_per_stream=20,
                           graceful=True) -> SimCellResult:
    """Rolling update: `streams` pinned task streams (node:<id> chunks)
    while every hosting shell is drained (graceful=True) or killed
    (graceful=False) one by one; the driver repins a stream when its host
    leaves. SLO (graceful): ZERO dropped streams — every chunk of every
    stream completes. The abrupt arm is the measured contrast: drops there
    are expected and must be TYPED."""
    import asyncio as _asyncio
    import random as _random

    from ray_tpu._private.simnode import SimCluster
    from ray_tpu.exceptions import NodeDiedError, RayTpuError

    res = SimCellResult(
        "rolling_update" if graceful else "rolling_update_abrupt",
        seed, num_nodes,
    )
    t0 = time.time()
    c = SimCluster(num_nodes, resources_per_node={"CPU": 4},
                   _system_config=_sim_config(), seed=seed)
    try:
        c.start()
        c.wait_for_view(timeout=60)
        rng = _random.Random(seed)
        hosts = rng.sample(
            [n for n in c.nodes if n not in c.entry_nodes], streams
        )
        pins = {i: hosts[i] for i in range(streams)}
        dropped: list = []
        typed_drops: list = []

        async def _stream(i):
            for _chunk in range(chunks_per_stream):
                node = pins[i]
                if node._draining or node._dead:
                    # Host is going away: repin to a live shell (the
                    # rolling-update driver's job).
                    node = rng.choice(c.alive_nodes())
                    pins[i] = node
                spec = c.make_spec(
                    sim_ms=10.0, strategy=f"node:{node.node_id}"
                )
                fut = c.register_waiter(spec.task_id)
                try:
                    await c.asubmit(spec)
                    await _asyncio.wait_for(fut, 3.0)
                except BaseException as e:  # noqa: BLE001 — typed below
                    c.discard_waiter(spec.task_id)
                    err = (
                        e
                        if isinstance(e, RayTpuError)
                        and not isinstance(e, TimeoutError)
                        else NodeDiedError(
                            f"stream {i} chunk lost: {type(e).__name__}"
                        )
                    )
                    dropped.append(i)
                    typed_drops.append(type(err).__name__)
                    return

        async def _run_streams():
            await _asyncio.gather(*[_stream(i) for i in range(streams)])

        def _roller():
            for host in hosts:
                time.sleep(0.25)
                if graceful:
                    c.drain_node(host)
                else:
                    c.kill_node(host)

        th = threading.Thread(target=_roller, daemon=True)
        th.start()
        c._io.run(_run_streams(), timeout=180)
        th.join(timeout=60)
        res.slo = {
            "streams": streams,
            "chunks_per_stream": chunks_per_stream,
            "dropped_streams": len(set(dropped)),
            "drop_types": sorted(set(typed_drops)),
            "graceful": graceful,
        }
        if graceful:
            res.ok = not dropped  # zero dropped streams on graceful drain
        else:
            # Abrupt arm: drops are expected but must be typed.
            res.ok = all(t == "NodeDiedError" for t in typed_drops)
        if not res.ok and res.error is None:
            res.error = f"slo violation: {res.slo}"
    except Exception as e:  # noqa: BLE001
        res.error = f"{type(e).__name__}: {e}"
    finally:
        c.shutdown()
    res.elapsed = time.time() - t0
    return res


def run_sim_matrix(num_nodes=96, seed=7, quick=False) -> list:
    """The sim-scale scorecard: one SimCellResult per cell. Seeded end to
    end — rerun with the same arguments to reproduce a scorecard."""
    n = max(32, num_nodes // 2) if quick else num_nodes
    return [
        run_sim_node_kill(num_nodes=n, seed=seed + 11,
                          kills=max(4, n // 12)),
        run_sim_partition_heal_storm(num_nodes=n, seed=seed + 23,
                                     victims=max(8, n // 4)),
        run_sim_rolling_update(num_nodes=max(32, n // 2), seed=seed + 37,
                               graceful=True),
        run_sim_rolling_update(num_nodes=max(32, n // 2), seed=seed + 37,
                               graceful=False),
    ]
