"""TPU collective group — XLA collectives over ICI.

TPU-native replacement for the reference's NCCLGroup
(python/ray/util/collective/collective_group/nccl_collective_group.py:127):
instead of NCCL communicators exchanged via ncclUniqueId, a group of member
processes (one actor per TPU host) forms a single XLA "world":

- rendezvous: rank 0 publishes the jax.distributed coordinator address in the
  GCS KV (exactly the reference's Rendezvous-via-named-store pattern,
  nccl_collective_group.py:28) and every member calls
  ``jax.distributed.initialize(coordinator, world_size, rank)``
- the group then materialises a ``jax.sharding.Mesh`` over the global device
  set — (processes × local chips) — and every collective op is a jitted
  ``shard_map`` program whose psum/all_gather/ppermute compile onto ICI
  (cross-slice traffic rides DCN via XLA multi-slice support)
- collectives are SPMD: every member must call the same op in the same order,
  the same contract NCCL imposes.

A world_size=1 group degenerates to the process's local device mesh — the
single-host multi-chip case where ICI collectives still apply but no
inter-process bootstrap is needed.
"""

from __future__ import annotations

import logging
import socket
import time

from ray_tpu.util.collective.types import ReduceOp

logger = logging.getLogger(__name__)

# Highest collective-group epoch this process has participated in, per group
# name: a member re-forming a group after destroy must not accept the dead
# epoch's coordinator from the KV (fresh processes start at 0 and accept the
# current epoch).
_last_epochs: dict = {}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))  # any-interface: the coordinator must be reachable from other hosts
    port = s.getsockname()[1]
    s.close()
    return port


def _routable_ip() -> str:
    """Best-effort primary-interface IP (UDP-connect trick; no packet sent)."""
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
        finally:
            s.close()
    except Exception:
        return "127.0.0.1"


class TpuCollectiveGroup:
    """One member's view of an XLA collective world."""

    def __init__(
        self,
        group_name: str,
        world_size: int,
        rank: int,
        coordinator: str | None = None,
        gcs=None,
        node_ip: str | None = None,
    ):
        import jax

        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        self.epoch = 0
        self._gcs = gcs
        self._node_ip = node_ip
        self._op_cache: dict = {}

        if world_size > 1:
            coordinator = coordinator or self._rendezvous(gcs)
            # jax.distributed.initialize refuses to run once the XLA backend
            # has been touched (e.g. a previous epoch of this group, or any
            # local jax work). Reset the backends HERE, at re-form time,
            # rather than in destroy(): live jax.Arrays and world_size=1
            # local-mesh groups in this process survive a destroy and only
            # die when a new multi-process world actually has to be built
            # (one process can host at most one such world).
            try:
                from jax._src import xla_bridge

                if xla_bridge.backends_are_initialized():
                    from jax.extend.backend import clear_backends

                    clear_backends()
            except Exception as e:
                logger.debug("backend reset before initialize: %s", e)
            # A SURVIVOR of a killed gang still holds the previous epoch's
            # distributed world (graceful destroy() shuts it down; a peer
            # SIGKILL doesn't). initialize() refuses to run twice per
            # process, so tear the stale world down here — bounded, because
            # shutdown() against a DEAD coordinator can hang in its
            # coordination-service handshake rather than raise. On timeout,
            # fail fast: this process cannot host a new world, and the gang
            # restart path (BackendExecutor) replaces it with a fresh one.
            import threading as _threading

            shut_done = _threading.Event()

            def _shutdown_stale():
                try:
                    jax.distributed.shutdown()
                except Exception as e:
                    logger.debug("stale distributed world shutdown: %s", e)
                finally:
                    shut_done.set()

            _threading.Thread(target=_shutdown_stale, daemon=True).start()
            if not shut_done.wait(15.0):
                raise RuntimeError(
                    "stale multi-process XLA world did not shut down "
                    "(previous epoch's coordinator dead?); this process "
                    "cannot host a new collective world — restart the gang "
                    "with fresh workers"
                )
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world_size,
                process_id=rank,
            )
        import numpy as np
        from jax.sharding import Mesh

        devices = np.array(jax.devices())
        self.local_device_count = len(jax.local_devices())
        self.devices = devices.reshape(world_size, -1)
        self.mesh = Mesh(self.devices, ("proc", "local"))
        logger.info(
            "collective group %s: rank %d/%d, %d global devices",
            group_name,
            rank,
            world_size,
            devices.size,
        )

    # ---- rendezvous via GCS KV (reference: Rendezvous in
    # nccl_collective_group.py:28, unique id in a named store actor) ----

    def _rendezvous(self, gcs) -> str:
        """Rank 0 advertises ``<routable-ip>:<port>`` under an epoch-scoped
        KV key; members poll the epoch counter, then the coordinator for
        that epoch. The epoch bump is what lets a destroyed group re-form
        under the same name (a member of a dead epoch can't accidentally
        dial a stale coordinator: re-init always publishes a fresh epoch,
        so a member that raced a stale read fails its connect, and the
        gang retry reads the new epoch)."""
        from ray_tpu._private.config import get_config

        assert gcs is not None, "GCS client required for multi-process rendezvous"
        epoch_key = f"collective/{self.group_name}/epoch"
        if self.rank == 0:
            resp = gcs.call("kv_get", {"key": epoch_key})
            epoch = int(bytes(resp["value"]).decode()) + 1 if resp.get("found") else 1
            # The node's GCS-registered address, NOT loopback: a rank on
            # another host must be able to dial this (reference advertises
            # ncclUniqueId the same way, nccl_collective_group.py:28).
            ip = self._node_ip or _routable_ip()
            if ip in ("0.0.0.0", ""):
                ip = _routable_ip()
            coordinator = f"{ip}:{_free_port()}"
            gcs.call("kv_put", {"key": f"collective/{self.group_name}/coord/{epoch}", "value": coordinator.encode()})
            gcs.call("kv_put", {"key": epoch_key, "value": str(epoch).encode()})
            self.epoch = epoch
            _last_epochs[self.group_name] = epoch
            return coordinator
        deadline = time.monotonic() + get_config().collective_rendezvous_timeout_s
        last_seen = _last_epochs.get(self.group_name, 0)
        candidate = None  # (epoch, address)
        while time.monotonic() < deadline:
            resp = gcs.call("kv_get", {"key": epoch_key})
            if resp.get("found"):
                epoch = int(bytes(resp["value"]).decode())
                if epoch > (candidate[0] if candidate else last_seen):
                    coord = gcs.call("kv_get", {"key": f"collective/{self.group_name}/coord/{epoch}"})
                    if coord.get("found"):
                        candidate = (epoch, bytes(coord["value"]).decode())
            if candidate is not None:
                # Liveness probe before handing the address to
                # jax.distributed.initialize: a stale key from a crashed
                # rank 0 (whose destroy never ran) would otherwise block the
                # whole init on a dead endpoint. The live rank 0 only starts
                # listening once IT calls initialize, so a refused connect
                # just means "keep polling" — a newer epoch supersedes.
                host, port = candidate[1].rsplit(":", 1)
                try:
                    s = socket.create_connection((host, int(port)), timeout=0.25)
                    s.close()
                    self.epoch = candidate[0]
                    _last_epochs[self.group_name] = candidate[0]
                    return candidate[1]
                except OSError:
                    pass
            time.sleep(0.05)
        raise TimeoutError(f"collective rendezvous for group {self.group_name} timed out")

    # ---- helpers ----

    def _global(self, x, partitioned: bool):
        """Lift this member's local tensor into the global mesh array.

        partitioned=False: x is this rank's full tensor (allreduce-style);
        global shape (world, *x.shape), sharded over 'proc', replicated local.
        """
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jnp.asarray(x)
        if self.world_size == 1:
            return x
        locals_ = [jax.device_put(x[None], d) for d in self.devices[self.rank]]
        global_shape = (self.world_size,) + x.shape
        return jax.make_array_from_single_device_arrays(
            global_shape, NamedSharding(self.mesh, P("proc")), locals_
        )

    def _local(self, out):
        """Extract this rank's addressable result (replicated output)."""
        import numpy as np

        if self.world_size == 1:
            return out
        shards = out.addressable_shards
        return shards[0].data if shards else np.asarray(out)

    def _jit_op(self, key, build):
        fn = self._op_cache.get(key)
        if fn is None:
            fn = build()
            self._op_cache[key] = fn
        return fn

    # ---- collectives (API parity with collective.py:258-594) ----

    def allreduce(self, x, op: ReduceOp = ReduceOp.SUM):
        import jax
        import jax.numpy as jnp
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        x = jnp.asarray(x)
        if self.world_size == 1:
            return x

        def build():
            def body(a):
                # a: (1, *shape) — this proc's copy.
                if op == ReduceOp.SUM:
                    r = lax.psum(a, "proc")
                elif op == ReduceOp.MEAN:
                    r = lax.pmean(a, "proc")
                elif op == ReduceOp.MAX:
                    r = lax.pmax(a, "proc")
                elif op == ReduceOp.MIN:
                    r = lax.pmin(a, "proc")
                elif op == ReduceOp.PRODUCT:
                    r = lax.all_gather(a, "proc").prod(axis=0)
                else:
                    raise ValueError(op)
                return r

            return jax.jit(
                jax.shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=P("proc"),
                    out_specs=P(),
                    check_vma=False,
                )
            )

        g = self._global(x, partitioned=False)
        out = self._jit_op(("allreduce", x.shape, str(x.dtype), op), build)(g)
        return self._local(out)[0]

    def allgather(self, x):
        """Returns the (world, *shape) stack of every rank's tensor."""
        import jax
        import jax.numpy as jnp
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        x = jnp.asarray(x)
        if self.world_size == 1:
            return x[None]

        def build():
            def body(a):
                return lax.all_gather(a, "proc", axis=0, tiled=True)

            return jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh, in_specs=P("proc"), out_specs=P(), check_vma=False
                )
            )

        g = self._global(x, partitioned=False)
        out = self._jit_op(("allgather", x.shape, str(x.dtype)), build)(g)
        return self._local(out)

    def reducescatter(self, x, op: ReduceOp = ReduceOp.SUM):
        """x: this rank's (world, chunk) stacked input; returns this rank's
        reduced chunk (x[rank] summed over ranks)."""
        import jax
        import jax.numpy as jnp
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        x = jnp.asarray(x)
        assert x.shape[0] == self.world_size, "leading dim must equal world size"
        if self.world_size == 1:
            return x[0]

        def build():
            def body(a):
                # a: (1, world, chunk...) per proc.
                r = lax.psum_scatter(a[0], "proc", scatter_dimension=0, tiled=False)
                return r[None]

            return jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh, in_specs=P("proc"), out_specs=P("proc"), check_vma=False
                )
            )

        g = self._global(x, partitioned=False)
        out = self._jit_op(("reducescatter", x.shape, str(x.dtype), op), build)(g)
        local = self._local(out)
        return local[0]

    def broadcast(self, x, src_rank: int = 0):
        import jax
        import jax.numpy as jnp
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        x = jnp.asarray(x)
        if self.world_size == 1:
            return x

        def build():
            def body(a):
                # Select src's copy on every proc: sum of masked copies.
                idx = lax.axis_index("proc")
                mask = (idx == src_rank).astype(a.dtype)
                return lax.psum(a * mask, "proc")

            return jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh, in_specs=P("proc"), out_specs=P(), check_vma=False
                )
            )

        g = self._global(x, partitioned=False)
        out = self._jit_op(("broadcast", x.shape, str(x.dtype), src_rank), build)(g)
        return self._local(out)[0]

    def reduce(self, x, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        # XLA worlds have no single-destination reduce; allreduce and let
        # non-destination ranks drop the value (same cost over ICI ring).
        out = self.allreduce(x, op)
        return out if self.rank == dst_rank else None

    def barrier(self):
        import jax.numpy as jnp

        self.allreduce(jnp.zeros((1,), jnp.float32))

    def send_recv(self, x, perm: list[tuple[int, int]]):
        """ppermute: pairwise exchange over the proc axis (the p2p primitive —
        reference collective.py:531/594 send/recv; on TPU this is the ring
        primitive ring-attention builds on)."""
        import jax
        import jax.numpy as jnp
        import jax.lax as lax
        from jax.sharding import PartitionSpec as P

        x = jnp.asarray(x)
        if self.world_size == 1:
            return x

        perm_t = tuple(tuple(p) for p in perm)

        def build():
            def body(a):
                return lax.ppermute(a, "proc", perm=perm_t)

            return jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh, in_specs=P("proc"), out_specs=P("proc"), check_vma=False
                )
            )

        g = self._global(x, partitioned=False)
        out = self._jit_op(("ppermute", x.shape, str(x.dtype), perm_t), build)(g)
        return self._local(out)[0]

    def send(self, value, dst_rank: int, tag: str) -> int:
        """2-party p2p send (reference: collective.py:531). In-program
        collectives ride ICI (ppermute above); this out-of-band object
        transfer uses the group's KV mailbox, and the receiver's device_put
        re-lands shards on its mesh — swap in a device-direct transfer here
        when jax exposes one (see util/collective/p2p.py)."""
        from ray_tpu.util.collective.p2p import mailbox_send

        return mailbox_send(self._gcs, self.group_name, self.rank, dst_rank, tag, value)

    def recv(self, src_rank: int, tag: str, timeout: float = 120.0):
        """2-party p2p recv (reference: collective.py:594)."""
        from ray_tpu.util.collective.p2p import mailbox_recv

        return mailbox_recv(self._gcs, self.group_name, src_rank, self.rank, tag, timeout)

    # ---- group payload verbs (device_object broadcast/reduce seam) ----
    #
    # IN-PROGRAM collectives already ride ICI (broadcast()/allreduce()/
    # reduce() above compile to psum variants over the mesh). The verbs
    # below move an OUT-OF-BAND payload — a sealed device object fanning
    # holder→members or combining across holders — and, like send/recv, use
    # the host plane until jax exposes a cross-process device-to-device
    # transfer in this image: swap the ICI/DMA group op in HERE (one
    # serialize → one ICI broadcast/allreduce over the group mesh) without
    # touching any caller (DeviceObjectManager.broadcast_via_group /
    # reduce_via_group). This seam now covers EVERY verb: on the tpu
    # backend the reducing payload verbs map straight onto the psum-based
    # collectives (the data is already on the mesh — no host relay tree
    # needed), which is exactly the swap the cpu tree emulates.

    def bcast_send_payload(self, value, tag: str, timeout: float = 30.0,
                           mailbox_fallback: bool = True) -> dict:
        from ray_tpu._private import worker_context
        from ray_tpu.util.collective.p2p import (
            fetch_member_addrs,
            fetch_roster,
            group_bcast_send,
        )

        cw = worker_context.get_core_worker()
        # The address cache is keyed on the ROSTER epoch (not the
        # coordinator epoch): a member that re-registered at the same
        # coordinator epoch — a respawn joining under its old rank — has a
        # new address under the same row, and only a roster bump says so.
        # Same cache shape as CpuCollectiveGroup._snapshot.
        roster = fetch_roster(self._gcs, self.group_name)
        repoch = roster["epoch"] if roster else 0
        cached = getattr(self, "_bcast_addrs", None)
        if cached is None or cached[0] != repoch:
            ranks = roster["ranks"] if roster else None
            world = max(self.world_size, roster["world_size"] if roster else 0)
            cached = self._bcast_addrs = (
                repoch,
                fetch_member_addrs(self._gcs, self.group_name, world, ranks=ranks),
            )
        world = max(self.world_size, roster["world_size"] if roster else 0)
        return group_bcast_send(
            cw, self._gcs, self.group_name, self.rank, world, tag,
            value, member_addrs=cached[1], timeout=timeout,
            mailbox_fallback=mailbox_fallback, roster=roster,
        )

    def bcast_recv_payload(self, src_rank: int, tag: str, timeout: float = 120.0):
        from ray_tpu._private import worker_context
        from ray_tpu.util.collective.p2p import group_bcast_recv

        cw = worker_context.get_core_worker()
        return group_bcast_recv(
            cw, self._gcs, self.group_name, src_rank, self.rank, tag, timeout
        )

    def reduce_send_payload(self, value, tag: str, op: ReduceOp = ReduceOp.SUM,
                            dst_rank: int = 0, timeout: float = 60.0):
        """Out-of-band group reduce on the tpu backend: the members' arrays
        live on the SAME mesh, so the combine IS a psum — no host relay
        tree. ``tag``/``timeout`` are accepted for cpu-seam parity (the
        gang rendezvous is the compiled program itself)."""
        return self.reduce(value, dst_rank, op)

    def allreduce_payload(self, value, tag: str, op: ReduceOp = ReduceOp.SUM,
                          timeout: float = 60.0):
        """Out-of-band group allreduce: psum over ICI (see seam note)."""
        return self.allreduce(value, op)

    def destroy(self):
        """Tear down the XLA world so the group can re-form (gang restart):
        drops the compiled-op cache, shuts down jax.distributed (releasing
        the coordinator connection), and best-effort clears this epoch's
        coordinator key. The next init under the same name bumps the epoch
        (SURVEY.md hard part #1: group epochs + restart-the-group recovery)."""
        import jax

        self._op_cache.clear()
        if self._gcs is not None:
            from ray_tpu.util.collective.p2p import roster_leave, unregister_member_addr

            try:
                roster_leave(self._gcs, self.group_name, self.rank)
            except Exception:
                pass
            unregister_member_addr(self._gcs, self.group_name, self.rank)
        if self.world_size > 1:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # already down / never initialized
                logger.debug("jax.distributed.shutdown: %s", e)
            if self.rank == 0 and self._gcs is not None:
                # Sweep this epoch's coordinator row AND the dead-epoch
                # rows behind it (every re-formation leaked its
                # predecessor's coord/<e> before), plus the roster rows
                # and orphaned addr rows — KV back to baseline.
                from ray_tpu.util.collective.p2p import sweep_group_kv

                for e in range(max(1, self.epoch - 16), self.epoch + 1):
                    try:
                        self._gcs.call("kv_del", {"key": f"collective/{self.group_name}/coord/{e}"})
                    except Exception:
                        pass
                try:
                    sweep_group_kv(self._gcs, self.group_name, self.world_size)
                except Exception:
                    pass
