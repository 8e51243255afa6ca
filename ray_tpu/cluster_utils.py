"""Multi-raylet single-host test cluster.

Analog of the reference's cluster_utils.Cluster (python/ray/cluster_utils.py:99,
add_node :165, remove_node :238): additional raylets on the same host, each
pretending to be a distinct node (own resources, own shm arena, shared GCS) —
the key multi-node-without-a-cluster trick the reference's failure tests rely
on. ``remove_node`` simulates node death for chaos tests.
"""

from __future__ import annotations

import os
import time

from ray_tpu._private.config import init_config
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet


class Cluster:
    def __init__(self, _system_config: dict | None = None):
        init_config(_system_config)
        self.gcs = GcsServer()
        self.session_dir = os.path.join("/tmp/ray_tpu", f"cluster_{os.getpid()}_{int(time.time())}")
        self.nodes: list[Raylet] = []
        self._connected = False
        # node_id -> (membrane id, workers severed) for partition_node.
        self._partitions: dict[str, tuple] = {}

    @property
    def gcs_address(self):
        return self.gcs.address

    def add_node(
        self,
        num_cpus: int = 1,
        num_tpus: int = 0,
        resources: dict | None = None,
        labels: dict | None = None,
        object_store_memory: int = 64 * 1024 * 1024,
    ) -> Raylet:
        node_resources = dict(resources or {})
        node_resources.setdefault("CPU", num_cpus)
        if num_tpus:
            node_resources.setdefault("TPU", num_tpus)
        raylet = Raylet(
            self.gcs.address,
            self.session_dir,
            resources=node_resources,
            labels=labels,
            object_store_memory=object_store_memory,
        )
        self.nodes.append(raylet)
        return raylet

    def connect(self, namespace: str = ""):
        """Attach the current process as a driver to the first node."""
        from ray_tpu._private import worker_context
        from ray_tpu._private.core_worker import DRIVER, CoreWorker

        assert self.nodes, "add_node() first"
        head = self.nodes[0]
        cw = CoreWorker(
            mode=DRIVER,
            gcs_address=self.gcs.address,
            raylet_address=head.address,
            arena_name=head.arena_name,
            node_id=head.node_id,
            session_dir=self.session_dir,
            namespace=namespace,
        )
        worker_context.set_core_worker(cw)
        self._connected = True
        return cw

    def remove_node(self, raylet: Raylet):
        """Simulate node death (reference: Cluster.remove_node for chaos tests)."""
        self.nodes.remove(raylet)
        raylet.stop()

    # ------------------------------------------------------------------
    # Crash faults (ISSUE 14): SIGKILL a process by ROLE. The in-process
    # raylets/GCS share the test process and cannot be SIGKILLed; worker
    # processes (plain workers, actors, serve replicas/proxies) are real
    # OS processes and can. The killer side stamps a ``chaos_kill`` flight
    # event so the injection shows up in the node postmortem exactly like
    # a plan-driven self-kill.
    # ------------------------------------------------------------------

    def _live_workers(self, raylet: Raylet | None = None):
        nodes = [raylet] if raylet is not None else self.nodes
        out = []
        for n in nodes:
            for w in n.workers.values():
                if not w.pid or w.state in ("starting", "dead"):
                    continue
                try:
                    # The raylet's monitor lags a SIGKILL by a poll tick;
                    # probe the pid so an already-dead worker (a previous
                    # cell's victim) is never picked again.
                    os.kill(w.pid, 0)
                except (ProcessLookupError, PermissionError):
                    continue
                out.append((n, w))
        return out

    def find_actor_worker(self, actor_name: str):
        """(raylet, WorkerHandle) hosting the named actor, or None. The
        GCS name registry maps name -> actor_id; raylets stamp actor_id on
        the worker the creation task landed in."""
        actor_id = next(
            (
                aid
                for (_ns, name), aid in self.gcs.named_actors.items()
                if name == actor_name
            ),
            None,
        )
        if actor_id is None:
            return None
        for n, w in self._live_workers():
            if w.actor_id == actor_id:
                return n, w
        return None

    def kill_role(self, role: str, raylet: Raylet | None = None, index: int = 0) -> int:
        """SIGKILL one process by role; returns the pid killed.

        - ``"worker"``: the ``index``-th live worker process (of ``raylet``
          when given, else cluster-wide, in node order).
        - ``"actor:<name>"``: the worker process hosting the named actor —
          serve replicas (``SERVE_REPLICA::<deployment>#<id>``) and proxies
          are actors, so this is the replica/proxy crash lever.
        """
        import signal

        from ray_tpu._private import chaos, flight_recorder

        if role.startswith("actor:"):
            found = self.find_actor_worker(role[6:])
            if found is None:
                raise ValueError(f"no live worker hosts actor {role[6:]!r}")
            _, w = found
        else:
            if role != "worker":
                raise ValueError(f"unknown role {role!r} (worker | actor:<name>)")
            workers = self._live_workers(raylet)
            if not workers:
                raise ValueError("no live worker processes to kill")
            _, w = workers[index % len(workers)]
        flight_recorder.record("chaos_kill", f"{role[:24]}:pid{w.pid}")
        chaos.CHAOS_STATS.injected += 1
        chaos.CHAOS_STATS.kills += 1
        os.kill(w.pid, signal.SIGKILL)
        return w.pid

    def install_plan_in_actor(
        self, actor_name: str, plan: dict | None, seed: int | None = None
    ) -> bool:
        """Push a chaos plan (None clears) into the worker PROCESS hosting
        the named actor — the seeded-kill lever for serve replicas: a
        ``kill`` rule on ``("actor_call", side="resp")`` makes the
        replica SIGKILL itself at its Nth answer, e.g. of a stream's
        ``next_stream_chunks`` polls (a proxy takes the chunks of all its
        streams on the replica in one such call: the kill fails them all)."""
        from ray_tpu._private.rpc import EventLoopThread

        found = self.find_actor_worker(actor_name)
        if found is None or found[1].client is None:
            return False
        io = EventLoopThread.get()
        io.run(
            found[1].client.acall(
                "chaos_set_plan", {"plan": plan, "seed": seed},
                timeout=5, retries=0,
            ),
            timeout=6,
        )
        return True

    def partition_node(self, raylet: Raylet, include_workers: bool = True):
        """In-process NETWORK TEAR: sever `raylet` from the rest of the
        cluster WITHOUT killing it (ROADMAP item 5's missing chaos lever —
        remove_node models death, this models a switch losing a port).

        Built on the chaos plane's membrane partition (chaos.py): the
        membrane's inside set is the node's endpoints (raylet + its
        registered workers), and any link crossing it fails with
        ConnectionLost — while node-LOCAL links (raylet <-> its own
        workers) stay up, like a real rack partition. Worker processes get
        their own membrane plan pushed first (they are separate OS
        processes; a plan here cannot see their sockets), with
        local_inside=True since they sit inside the membrane.

        Heal with heal_node() and the node rejoins: heartbeats resume, and
        if the partition outlived node_death_timeout_s the raylet
        re-registers + republishes its object locations (actors the GCS
        declared dead stay dead, per node-death semantics)."""
        from ray_tpu._private import chaos, rpc
        from ray_tpu._private.rpc import EventLoopThread

        inside = [rpc.addr_key(raylet.address)]
        workers = [
            w for w in raylet.workers.values()
            if w.address is not None and w.client is not None
            and w.state not in ("starting", "dead")
        ]
        inside += [rpc.addr_key(w.address) for w in workers]
        worker_plan = {
            "rules": [{"kind": "partition", "inside": inside, "local_inside": True}]
        }
        if include_workers:
            # Push the workers' plans BEFORE severing the driver side —
            # afterwards they are unreachable by construction.
            io = EventLoopThread.get()
            for w in workers:
                try:
                    io.run(
                        w.client.acall(
                            "chaos_set_plan", {"plan": worker_plan},
                            timeout=5, retries=0,
                        ),
                        timeout=6,
                    )
                except Exception:
                    pass  # a wedged worker is already chaos
        plan = chaos.ensure_plan()
        mid = plan.add_membrane(inside, local_inside=False)
        self._partitions[raylet.node_id] = (mid, workers)
        return mid

    def heal_node(self, raylet: Raylet):
        """Reverse partition_node: drop the membrane and clear the node's
        worker plans (reachable again). The raylet rejoins on its next
        heartbeat (or re-registers if it was declared dead meanwhile)."""
        from ray_tpu._private import chaos
        from ray_tpu._private.rpc import EventLoopThread

        entry = self._partitions.pop(raylet.node_id, None)
        if entry is None:
            return
        mid, workers = entry
        plan = chaos.active()
        if plan is not None:
            plan.remove_membrane(mid)
        io = EventLoopThread.get()
        for w in workers:
            try:
                io.run(
                    w.client.acall("chaos_set_plan", {"plan": None}, timeout=5, retries=0),
                    timeout=6,
                )
            except Exception:
                pass

    def restart_gcs(self) -> GcsServer:
        """Stop the GCS and bring a fresh one up on the SAME address (no
        persistence: the node table is gone). Every raylet's next heartbeat
        returns ``unknown`` and it re-registers with jittered backoff,
        republishing its object locations — the rejoin-storm path."""
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

    def wait_for_nodes(self, timeout: float = 10.0):
        deadline = time.monotonic() + timeout
        want = len(self.nodes)
        while time.monotonic() < deadline:
            from ray_tpu._private.rpc import EventLoopThread

            alive = sum(
                1 for n in self.gcs.nodes.values() if n["state"] == "ALIVE"
            )
            if alive >= want:
                return
            time.sleep(0.05)
        raise TimeoutError("cluster nodes did not come up")

    def shutdown(self):
        from ray_tpu._private import chaos, worker_context

        # A lingering fault plan (a test that partitioned and never healed)
        # must not outlive its cluster into the next test's traffic.
        if self._partitions or chaos.active() is not None:
            self._partitions.clear()
            chaos.clear()

        if self._connected:
            cw = worker_context.get_core_worker_if_initialized()
            if cw is not None:
                cw.shutdown()
                worker_context.set_core_worker(None)
        for raylet in self.nodes:
            raylet.stop()
        self.nodes.clear()
        self.gcs.stop()
