"""Control-plane scale simulation: hundreds of raylet shells in one process.

The real multi-node story (cluster_utils.Cluster) tops out around a dozen
raylets per box — each carries a shm arena, worker pool, object store, and
zygote. This module keeps everything the CONTROL PLANE sees real and stubs
only the data/execution plane:

- SimNode speaks the real GCS wire protocol over real sockets: register,
  versioned delta-sync heartbeats, rejoin with jittered backoff,
  object-location publish — the same code paths (``apply_heartbeat_view``,
  ``rejoin_backoff_delay``, ``ArgLocalityCache``) the production raylet runs.
- Each shell owns a real ``sched_core`` ledger mirroring the cluster view and
  places tasks with the same locality-then-hybrid policy, spilling over real
  peer RPC (bounded hops, like raylet spillback).
- The EXECUTOR is a stub: a task "runs" by holding its resources for a
  modeled duration on the event-loop timer, then releasing them. No worker
  process, no user code, no object payloads — completions are reported
  through an in-process callback, not the owner wire path (the honest
  fidelity gap; see PARITY.md).

That trade buys 1k nodes on one box: enough to drive GCS fan-in (heartbeat
reply bytes, node-death directory scans, task-event ingest) and the chaos
matrix at a scale where O(N^2) control-plane behavior is measurable, not
theoretical. See ``tests/test_simnode.py`` and ``tests/chaos_matrix.py``.
"""

from __future__ import annotations

import asyncio
import collections
import itertools
import logging
import math
import random
import time

from ray_tpu._private import flight_recorder
from ray_tpu._private.config import get_config, init_config
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import (
    ArgLocalityCache,
    OptimisticDebitLedger,
    apply_heartbeat_view,
    rejoin_backoff_delay,
)
from ray_tpu._private.rpc import EventLoopThread, RpcClient, RpcServer
from ray_tpu._private.sched_core import HYBRID, SPREAD, create_sched_core
from ray_tpu._private.task_spec import TaskSpec
from ray_tpu.exceptions import NodeDiedError, RayTpuError

logger = logging.getLogger(__name__)

# Spillback hop cap: a task bounced between saturated shells executes at the
# cap-holder instead of ping-ponging (the raylet path gets the same effect
# from queue-at-feasible semantics).
_MAX_SIM_HOPS = 3


def _percentile(samples: list, q: float) -> float:
    """Nearest-rank percentile; 0.0 on empty input."""
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[min(len(s) - 1, int(math.ceil(q * len(s))) - 1)]


class SimNode:
    """One lightweight raylet shell.

    Real: GCS wire protocol (own RpcClient), RPC server (own listen socket,
    spillback target), sched_core ledger, delta-sync cluster view, locality
    cache, rejoin backoff. Stub: the executor — ``_start_exec`` holds the
    task's resources for ``runtime_env["sim_ms"]`` modeled milliseconds on
    the event-loop timer, then releases and reports via the in-process
    ``on_task_done`` callback.

    All task-path state (queue, timers, ledger) is touched ONLY from the
    process's IO loop (RPC handlers + timer callbacks + coroutines spawned
    there); driver-thread levers go through SimCluster, which hops onto the
    loop first.
    """

    def __init__(
        self,
        gcs_address,
        index: int,
        resources: dict | None = None,
        on_task_done=None,
    ):
        self.cfg = get_config()
        self.index = index
        # Deterministic hex id: stable across runs for seeded chaos cells.
        self.node_id = f"{index:032x}"
        self.resources_total = dict(resources or {"CPU": 4})
        self._sched = create_sched_core()
        self.cluster_view: dict[str, dict] = {}
        self._synced_peers: set[str] = set()
        self._view_version = 0
        self._rejoin_rng = random.Random(self.node_id)
        self._rejoin_attempts = 0
        self.on_task_done = on_task_done
        # Objects this shell "holds" — the modeled data plane. Locations are
        # published to the GCS for real, so locality lookups resolve.
        self.local_objects: set[str] = set()
        self.queue: collections.deque = collections.deque()
        # Hard-pinned (node:<id>) tasks whose target left the view: parked,
        # re-placed on view refresh (the node may rejoin) — NEVER run
        # locally, that would silently violate the pin.
        self.infeasible: list = []
        self.running = 0
        self.completed = 0
        self.forwarded = 0
        self.locality_hits = 0
        self.placement_s: list[float] = []
        self._dead = False
        self._draining = False
        self._partitioned = False
        self._timers: dict[str, asyncio.TimerHandle] = {}
        self._io = EventLoopThread.get()
        self._loop = self._io.loop
        self.server = RpcServer(f"sim{index}")
        self.server.register_all(self)
        self.server.start("127.0.0.1", 0)
        self.address = self.server.address
        self.gcs = RpcClient(gcs_address, label=f"sim{index}->gcs")
        self._arg_locality = ArgLocalityCache(self.gcs, self.cfg)
        self._opt_debits = OptimisticDebitLedger()
        self._peers: dict[str, RpcClient] = {}
        self._hb_task: asyncio.Future | None = None

    # ------------------------------------------------------------------
    # Membership: register / heartbeat / rejoin — the real wire protocol.
    # ------------------------------------------------------------------

    @property
    def resources_available(self) -> dict:
        return {
            k: self._sched.node_avail(self.node_id, k) for k in self.resources_total
        }

    async def start(self):
        self._sched.node_upsert(
            self.node_id, self.resources_total, dict(self.resources_total)
        )
        await self._register()
        for oid in list(self.local_objects):
            await self._publish_location(oid)
        self._hb_task = asyncio.ensure_future(self._heartbeat_loop())

    async def _register(self):
        await self.gcs.acall(
            "register_node",
            {
                "node_id": self.node_id,
                "address": list(self.address),
                "resources": self.resources_total,
                "labels": {"sim": "1"},
            },
        )

    async def _publish_location(self, oid: str):
        try:
            await self.gcs.acall(
                "add_object_location", {"object_id": oid, "node_id": self.node_id}
            )
        except Exception:
            pass  # GCS unreachable: the next rejoin republishes

    async def _heartbeat_loop(self):
        # De-synchronized start: 1k shells created in a tight loop must not
        # all heartbeat in the same millisecond every interval (the real
        # fleet is naturally staggered by boot time).
        await asyncio.sleep(
            self._rejoin_rng.uniform(0, self.cfg.heartbeat_interval_s)
        )
        while not self._dead:
            try:
                if not self._partitioned:
                    hb = {
                        "node_id": self.node_id,
                        "resources_available": self.resources_available,
                        "view_version": self._view_version,
                    }
                    resp = await self.gcs.acall("heartbeat", hb, timeout=5, retries=0)
                    if resp.get("dead") or resp.get("unknown"):
                        # Declared dead (partition outlived the death timeout)
                        # or the GCS restarted and lost its node table.
                        await self._rejoin()
                        continue
                    apply_heartbeat_view(resp, self)
                    self._opt_debits.expire(self._sched)
                    self._rejoin_attempts = 0
                    await self._reschedule_queue()  # view refreshed
            except Exception:
                pass  # unreachable GCS: keep the cadence, try next interval
            await asyncio.sleep(self.cfg.heartbeat_interval_s)

    async def _rejoin(self):
        """Same contract as Raylet._rejoin: jittered backoff, re-register
        under the same node id, republish held object locations (the GCS
        dropped our rows at death)."""
        delay = rejoin_backoff_delay(self._rejoin_attempts, self.cfg, self._rejoin_rng)
        self._rejoin_attempts += 1
        if delay > 0:
            await asyncio.sleep(delay)
        await self._register()
        self._view_version = 0  # force a full-view resync on the next beat
        for oid in list(self.local_objects):
            await self._publish_location(oid)

    # ------------------------------------------------------------------
    # Task path: real placement, real spillback RPC, stub execution.
    # ------------------------------------------------------------------

    async def rpc_submit_task(self, req):
        if self._dead:
            raise NodeDiedError(f"sim node {self.node_id[:8]} is dead")
        spec = TaskSpec.from_wire(req["spec"])
        await self._queue_and_schedule(spec)
        return {"ok": True, "node_id": self.node_id}

    async def rpc_sim_stats(self, req):
        return {
            "node_id": self.node_id,
            "completed": self.completed,
            "running": self.running,
            "queued": len(self.queue),
            "forwarded": self.forwarded,
            "view_nodes": len(self.cluster_view),
            "view_version": self._view_version,
        }

    async def _queue_and_schedule(self, spec: TaskSpec):
        prefer = await self._locality_prefs(spec)
        target = self._pick_node(spec, prefer=prefer)
        if target is None:
            if (spec.scheduling_strategy or "").startswith("node:"):
                self.infeasible.append(spec)
            else:
                self._queue_local(spec)
            return
        if target == self.node_id:
            self._queue_local(spec)
            return
        hops = int(spec.runtime_env.get("sim_hops", 0))
        row = self.cluster_view.get(target)
        if hops >= _MAX_SIM_HOPS or row is None:
            self._queue_local(spec)
            return
        spec.runtime_env["sim_hops"] = hops + 1
        self.forwarded += 1
        # Optimistic mirror debit (same as Raylet._queue_and_schedule): a
        # burst must spread over fits-now peers, not dogpile the first one.
        # An authoritative heartbeat row overwrites it; the debit ledger
        # credits it back if none arrives (quiet peers send no delta rows).
        if self._sched.try_acquire(target, spec.resources):
            self._opt_debits.note(target, spec.resources, self.cfg.heartbeat_interval_s)
        try:
            await self._peer(target, row["address"]).acall(
                "submit_task", {"spec": spec.to_wire()}, timeout=10, retries=1
            )
        except Exception:
            # Peer died/partitioned mid-forward: keep the task here — it
            # queues until local resources free (or the driver's timeout
            # fires and the closed-loop user resubmits, typed).
            self._queue_local(spec)

    def _peer(self, node_id: str, address) -> RpcClient:
        client = self._peers.get(node_id)
        if client is None:
            client = RpcClient(
                tuple(address), label=f"sim{self.index}->peer"
            )
            self._peers[node_id] = client
        return client

    def _queue_local(self, spec: TaskSpec):
        self.queue.append(spec)
        self._drain_queue()

    async def _reschedule_queue(self):
        """Heartbeat-tick queue maintenance: drain whatever now fits
        locally, then re-run placement for head-blocked tasks that still
        have spill hops left — peers that freed up since the last view are
        only visible after a refresh (the raylet gets the same effect from
        _requeue_infeasible + _dispatch on its heartbeat)."""
        self._drain_queue()
        if self.infeasible:
            parked, self.infeasible = self.infeasible, []
            for spec in parked:
                await self._queue_and_schedule(spec)
        if not self.queue:
            return
        movable = [
            s
            for s in self.queue
            if int(s.runtime_env.get("sim_hops", 0)) < _MAX_SIM_HOPS
        ]
        if not movable:
            return
        kept = [
            s
            for s in self.queue
            if int(s.runtime_env.get("sim_hops", 0)) >= _MAX_SIM_HOPS
        ]
        self.queue.clear()
        self.queue.extend(kept)
        for spec in movable:
            await self._queue_and_schedule(spec)

    def _drain_queue(self):
        while self.queue and not self._dead:
            spec = self.queue[0]
            if not self._sched.try_acquire(self.node_id, spec.resources):
                return  # head blocked: FIFO per shell, like the raylet queue
            self.queue.popleft()
            self._start_exec(spec)

    def _start_exec(self, spec: TaskSpec):
        """Stub executor: resources held for the modeled duration, then
        released by a loop timer. Placement latency is measured HERE — the
        control-plane job is done once resources are acquired on a node."""
        submit = spec.hop_ts.get("sim_submit")
        if submit is not None:
            self.placement_s.append(time.monotonic() - submit)
        self.running += 1
        dur_s = max(0.0, float(spec.runtime_env.get("sim_ms", 1.0))) / 1000.0
        self._timers[spec.task_id] = self._loop.call_later(
            dur_s, self._finish_exec, spec
        )

    def _finish_exec(self, spec: TaskSpec):
        self._timers.pop(spec.task_id, None)
        if self._dead:
            return  # killed mid-flight: resources are gone with the node
        self._sched.release(self.node_id, spec.resources)
        self.running -= 1
        self.completed += 1
        for oid in spec.runtime_env.get("sim_creates", ()):
            # The task "produced" these objects: this shell becomes a
            # holder and publishes the location for real — downstream
            # locality decisions resolve against live GCS rows.
            self.local_objects.add(oid)
            asyncio.ensure_future(self._publish_location(oid))
        if self.on_task_done is not None:
            self.on_task_done(self.node_id, spec)
        self._drain_queue()

    # ------------------------------------------------------------------
    # Placement: the raylet's policy, verbatim semantics.
    # ------------------------------------------------------------------

    def _pick_node(self, spec: TaskSpec, prefer: list | None = None) -> str | None:
        strategy = spec.scheduling_strategy or "DEFAULT"
        if strategy.startswith("node:"):
            parts = strategy.split(":")
            node_id = parts[1]
            soft = len(parts) > 2 and parts[2] == "soft"
            if node_id == self.node_id or node_id in self.cluster_view:
                return node_id
            return self.node_id if soft else None
        if prefer:
            for nid in prefer:
                if nid == self.node_id:
                    if self._fits_now(spec):
                        self._note_locality_hit(spec, nid)
                        return nid
                elif nid in self.cluster_view and self._sched.node_fits(
                    nid, spec.resources
                ):
                    self._note_locality_hit(spec, nid)
                    return nid
        policy = SPREAD if strategy == "SPREAD" else HYBRID
        return self._sched.best_node(spec.resources, policy, self.node_id)

    def _fits_now(self, spec: TaskSpec) -> bool:
        return all(
            self._sched.node_avail(self.node_id, k) >= v - 1e-9
            for k, v in spec.resources.items()
            if v > 0
        )

    def _note_locality_hit(self, spec: TaskSpec, nid: str):
        self.locality_hits += 1
        flight_recorder.record("locality_hit", f"{spec.task_id[:8]}->{nid[:8]}")

    async def _locality_prefs(self, spec: TaskSpec) -> list | None:
        if (spec.scheduling_strategy or "DEFAULT") != "DEFAULT":
            return None
        if len(self.cluster_view) <= 1:
            return None
        counts = await self._arg_locality.holders(spec)
        if not counts:
            return None
        return sorted(counts, key=lambda n: -counts[n])

    # ------------------------------------------------------------------
    # Chaos levers (loop-side halves; SimCluster hops threads).
    # ------------------------------------------------------------------

    def partition(self, on: bool = True):
        """Suppress heartbeats (and let inbound submits keep failing via
        peer timeouts) — models a switch losing the port. Past
        node_death_timeout_s the GCS declares the node dead; on heal the
        next heartbeat returns ``dead`` and the shell rejoins with backoff."""
        self._partitioned = on

    async def drain(self):
        """Graceful removal: the GCS tombstones the node out of the ALIVE
        view (peers stop spilling here), queued + in-flight stub tasks run
        to completion."""
        self._draining = True
        await self.gcs.acall("drain_node", {"node_id": self.node_id})

    async def akill(self):
        """Abrupt death, loop side: heartbeats stop, in-flight completions
        are cancelled (they never report), the queue is dropped. Drivers
        see timeouts and resubmit — typed, per SimTraffic's contract."""
        self._dead = True
        if self._hb_task is not None:
            self._hb_task.cancel()
        for handle in self._timers.values():
            handle.cancel()
        self._timers.clear()
        self.queue.clear()
        self.infeasible.clear()

    async def aclose_clients(self):
        try:
            self.gcs.close()
        except Exception:
            pass
        for client in self._peers.values():
            try:
                client.close()
            except Exception:
                pass
        self._peers.clear()

    def stop(self):
        """Full teardown; DRIVER thread only (server.stop hops the loop)."""
        self._io.run(self.akill())
        self.server.stop()
        self._io.run(self.aclose_clients())
        self._sched.close()


class SimCluster:
    """A GcsServer plus N SimNode shells in this process.

    Shells register over the real wire in batches; task submission enters
    through a bounded set of entry shells (round-robin), mirroring drivers
    connecting to their local raylet. Completion is observed via the
    in-process ``on_task_done`` callback feeding per-task waiters.
    """

    def __init__(
        self,
        num_nodes: int,
        resources_per_node: dict | None = None,
        _system_config: dict | None = None,
        seed: int = 0,
        num_entry_nodes: int = 16,
    ):
        if _system_config is not None:
            init_config(_system_config)
        self.cfg = get_config()
        self.gcs = GcsServer()
        self.seed = seed
        self._io = EventLoopThread.get()
        self.results: dict[str, str] = {}  # task_id -> completing node_id
        self._done_count = 0
        self._waiters: dict[str, asyncio.Future] = {}
        self._task_ids = itertools.count(1)
        self.nodes: list[SimNode] = [
            SimNode(
                self.gcs.address,
                i,
                resources=resources_per_node,
                on_task_done=self._on_done,
            )
            for i in range(num_nodes)
        ]
        self.entry_nodes = self.nodes[: max(1, min(num_entry_nodes, num_nodes))]
        self._entry_rr = itertools.cycle(range(len(self.entry_nodes)))
        self._entry_clients: dict[str, RpcClient] = {}

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    def start(self, batch: int = 64, timeout: float = 120.0):
        """Register every shell with the GCS, ``batch`` at a time (the real
        fleet's boot is staggered; an unbatched 1k-wide gather is also just
        slow to error out of)."""
        for i in range(0, len(self.nodes), batch):
            chunk = self.nodes[i : i + batch]
            self._io.run(self._start_batch(chunk), timeout=timeout)

    @staticmethod
    async def _start_batch(chunk: list):
        await asyncio.gather(*[n.start() for n in chunk])

    def wait_for_view(self, min_nodes: int | None = None, timeout: float = 30.0):
        """Block until every live shell's delta-synced cluster view holds at
        least ``min_nodes`` rows (default: all registered shells)."""
        want = min_nodes if min_nodes is not None else len(self.nodes)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            lagging = [
                n
                for n in self.nodes
                if not n._dead and not n._partitioned and len(n.cluster_view) < want
            ]
            if not lagging:
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"{len(lagging)} sim shells never converged to a {want}-node view"
        )

    def shutdown(self):
        for i in range(0, len(self.nodes), 64):
            chunk = self.nodes[i : i + 64]
            self._io.run(self._kill_batch(chunk), timeout=30)
        for node in self.nodes:
            node.server.stop()
        for node in self.nodes:
            self._io.run(node.aclose_clients(), timeout=10)
            node._sched.close()
        for client in self._entry_clients.values():
            try:
                client.close()
            except Exception:
                pass
        self._entry_clients.clear()
        self.gcs.stop()

    @staticmethod
    async def _kill_batch(chunk: list):
        for n in chunk:
            await n.akill()

    # ------------------------------------------------------------------
    # Submission / completion
    # ------------------------------------------------------------------

    def make_spec(
        self,
        resources: dict | None = None,
        sim_ms: float = 1.0,
        args: list | None = None,
        strategy: str = "DEFAULT",
        creates: list | None = None,
    ) -> TaskSpec:
        runtime_env: dict = {"sim_ms": sim_ms}
        if creates:
            runtime_env["sim_creates"] = list(creates)
        return TaskSpec(
            task_id=f"t{next(self._task_ids):015d}",
            job_id="sim",
            name="sim_task",
            args=list(args or []),
            resources=dict(resources or {"CPU": 1}),
            scheduling_strategy=strategy,
            runtime_env=runtime_env,
        )

    def _entry_client(self, node: SimNode) -> RpcClient:
        client = self._entry_clients.get(node.node_id)
        if client is None:
            client = RpcClient(tuple(node.address), label="sim-driver")
            self._entry_clients[node.node_id] = client
        return client

    def next_entry(self) -> SimNode:
        return self.entry_nodes[next(self._entry_rr)]

    async def asubmit(self, spec: TaskSpec, entry: SimNode | None = None):
        """Submit over the real wire through an entry shell. Stamps the
        placement clock; the executing shell measures submit->acquire."""
        spec.hop_ts["sim_submit"] = time.monotonic()
        node = entry if entry is not None else self.next_entry()
        await self._entry_client(node).acall(
            "submit_task", {"spec": spec.to_wire()}, timeout=10, retries=1
        )

    def register_waiter(self, task_id: str) -> asyncio.Future:
        """Loop-side: create the completion future BEFORE submitting, so a
        fast completion can't race past its waiter."""
        fut = self._loop_future()
        self._waiters[task_id] = fut
        return fut

    def _loop_future(self) -> asyncio.Future:
        return asyncio.get_running_loop().create_future()

    def discard_waiter(self, task_id: str):
        self._waiters.pop(task_id, None)

    def _on_done(self, node_id: str, spec: TaskSpec):
        # Runs on the IO loop (timer callback chain).
        self.results[spec.task_id] = node_id
        self._done_count += 1
        fut = self._waiters.pop(spec.task_id, None)
        if fut is not None and not fut.done():
            fut.set_result(node_id)

    @property
    def done_count(self) -> int:
        return self._done_count

    def wait_done(self, n: int, timeout: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._done_count >= n:
                return True
            time.sleep(0.02)
        return self._done_count >= n

    # ------------------------------------------------------------------
    # Chaos levers (driver-thread wrappers)
    # ------------------------------------------------------------------

    def kill_node(self, node: SimNode):
        flight_recorder.record("chaos_kill", f"simnode:{node.node_id[:8]}")
        self._io.run(node.akill())
        node.server.stop()

    def drain_node(self, node: SimNode):
        self._io.run(node.drain(), timeout=10)

    def partition_node(self, node: SimNode, on: bool = True):
        node.partition(on)

    def restart_gcs(self) -> GcsServer:
        """Stop the GCS and bring a fresh one up on the SAME address: every
        shell's next heartbeat hits ``unknown`` and rejoins — the rejoin
        storm the jittered backoff exists to flatten."""
        host, port = self.gcs.address
        self.gcs.stop()
        deadline = time.monotonic() + 10
        while True:
            try:
                self.gcs = GcsServer(host, port)
                return self.gcs
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)

    def seed_object(self, node: SimNode, oid: str):
        """Make ``node`` a holder of ``oid`` (modeled payload) and publish
        the location for real — locality tests build on this."""
        node.local_objects.add(oid)
        self._io.run(node._publish_location(oid), timeout=10)

    # ------------------------------------------------------------------
    # SLO material
    # ------------------------------------------------------------------

    def placement_latencies(self) -> list[float]:
        out: list[float] = []
        for n in self.nodes:
            out.extend(n.placement_s)
        return out

    def placement_p99_ms(self) -> float:
        return _percentile(self.placement_latencies(), 0.99) * 1000.0

    def alive_nodes(self) -> list[SimNode]:
        return [n for n in self.nodes if not n._dead and not n._draining]


class SimTraffic:
    """Closed-loop synthetic load with diurnal/bursty modulation.

    ``users`` concurrent loops each do submit -> await completion -> think.
    Think time is modulated over ``period_s``: ``diurnal`` sweeps a sine
    (smooth peak/trough), ``bursty`` a square wave (quiet half, 10x half).
    Everything is seeded — a scorecard reproduces from its seed.

    Failure contract: every failure a user observes is TYPED. A completion
    that never arrives (killed shell, dropped queue) or a submit into a dead
    entry surfaces as NodeDiedError — never a raw TimeoutError — is counted,
    and the task is resubmitted through a different entry (closed-loop
    retry, like a driver failing over its raylet connection).
    """

    def __init__(
        self,
        cluster: SimCluster,
        users: int = 8,
        pattern: str = "diurnal",
        period_s: float = 4.0,
        think_s: float = 0.02,
        sim_ms: float = 2.0,
        task_timeout_s: float = 5.0,
        resources: dict | None = None,
        seed: int = 1,
    ):
        assert pattern in ("diurnal", "bursty", "flat")
        self.cluster = cluster
        self.users = users
        self.pattern = pattern
        self.period_s = period_s
        self.think_s = think_s
        self.sim_ms = sim_ms
        self.task_timeout_s = task_timeout_s
        self.resources = dict(resources or {"CPU": 1})
        self.seed = seed

    def run(self, duration_s: float) -> dict:
        return self.cluster._io.run(
            self._run(duration_s), timeout=duration_s + 120
        )

    async def _run(self, duration_s: float) -> dict:
        stats = {
            "completed": 0,
            "submitted": 0,
            "resubmits": 0,
            "failures": {},
            "pattern": self.pattern,
            "users": self.users,
            "seed": self.seed,
        }
        t0 = time.monotonic()
        await asyncio.gather(
            *[self._user(i, t0, duration_s, stats) for i in range(self.users)]
        )
        stats["wall_s"] = time.monotonic() - t0
        return stats

    def _mult(self, t: float) -> float:
        phase = (t % self.period_s) / self.period_s
        if self.pattern == "bursty":
            return 0.1 if phase < 0.5 else 1.9
        if self.pattern == "diurnal":
            return 1.0 + 0.8 * math.sin(2 * math.pi * phase)
        return 1.0

    async def _user(self, idx: int, t0: float, duration_s: float, stats: dict):
        rng = random.Random((self.seed << 16) + idx)
        entries = self.cluster.entry_nodes
        while time.monotonic() - t0 < duration_s:
            await self._submit_once(rng, entries, stats)
            think = self.think_s * self._mult(time.monotonic() - t0)
            await asyncio.sleep(max(0.001, think * rng.uniform(0.5, 1.5)))

    async def _submit_once(self, rng, entries, stats, max_attempts: int = 3):
        for attempt in range(max_attempts):
            spec = self.cluster.make_spec(
                resources=self.resources, sim_ms=self.sim_ms
            )
            fut = self.cluster.register_waiter(spec.task_id)
            stats["submitted"] += 1
            entry = entries[rng.randrange(len(entries))]
            try:
                await self.cluster.asubmit(spec, entry=entry)
                await asyncio.wait_for(fut, self.task_timeout_s)
                stats["completed"] += 1
                return True
            except BaseException as e:  # noqa: BLE001 — typed below
                self.cluster.discard_waiter(spec.task_id)
                err = self._typed(e)
                name = type(err).__name__
                stats["failures"][name] = stats["failures"].get(name, 0) + 1
                if attempt + 1 < max_attempts:
                    stats["resubmits"] += 1
                    entries = self.cluster.alive_nodes() or self.cluster.entry_nodes
        return False

    @staticmethod
    def _typed(e: BaseException) -> RayTpuError:
        """Every user-visible failure is a RayTpuError subclass. A lost
        completion (timeout) or severed entry connection means the hosting
        shell died or was partitioned: NodeDiedError."""
        if isinstance(e, RayTpuError) and not isinstance(e, TimeoutError):
            return e
        return NodeDiedError(f"sim task lost to node failure: {type(e).__name__}")
