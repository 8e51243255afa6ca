"""Node — session bootstrap and daemon lifecycle.

TPU-native analog of the reference's Node/process-tree launcher
(python/ray/_private/node.py:37, start_ray_processes node.py:1186,
services.py): creates the session directory and brings up the GCS and the
node's raylet.

Deviation from the reference (documented): daemons run in-process on the IO
event-loop thread rather than as separate OS processes — every interaction
still crosses a real socket, so the distributed protocol is identical and
multi-raylet "clusters" on one host (the reference's cluster_utils.Cluster
trick, python/ray/cluster_utils.py:99) work the same way; worker processes are
real subprocesses either way. `gcs.py`/`raylet.py` keep standalone `main()`s
for out-of-process deployment.

TPU detection counts the chips' device nodes (/dev/accel<n>, or
/dev/vfio/<n> on hosts that pass chips through VFIO, v5e among them) —
deliberately without importing jax, because initialising the TPU runtime in
the driver would take the host's TPU client lock and starve worker processes
(see SURVEY.md §7 hard part 5).
"""

from __future__ import annotations

import glob
import os
import time

from ray_tpu._private.config import get_config, init_config
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.raylet import Raylet


def detect_tpu_chips() -> int:
    if os.environ.get("RAY_TPU_NUM_TPUS"):
        return int(os.environ["RAY_TPU_NUM_TPUS"])
    return len(glob.glob("/dev/accel*")) or sum(
        os.path.basename(p).isdigit() for p in glob.glob("/dev/vfio/*")
    )


def pinned_jax_platform() -> str:
    """The platform the environment's JAX_PLATFORMS puts jax on ("" when it is
    not set) — like the chip count, learnt without importing jax."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()


def detect_tpu_labels() -> dict:
    labels = {}
    env_type = os.environ.get("TPU_ACCELERATOR_TYPE") or os.environ.get("ACCELERATOR_TYPE")
    if env_type:
        labels["tpu_accelerator_type"] = env_type
    worker_id = os.environ.get("TPU_WORKER_ID")
    if worker_id:
        labels["tpu_worker_id"] = worker_id
    return labels


class Node:
    def __init__(
        self,
        head: bool = True,
        gcs_address=None,
        num_cpus: int | None = None,
        num_tpus: int | None = None,
        resources: dict | None = None,
        object_store_memory: int | None = None,
        labels: dict | None = None,
        session_dir: str | None = None,
        _system_config: dict | None = None,
    ):
        cfg = init_config(_system_config) if head else get_config()
        ts = time.strftime("%Y%m%d-%H%M%S")
        import uuid as _uuid

        # uuid suffix: two inits in the same process+second (common in test
        # suites) must not share a session directory.
        self.session_dir = session_dir or os.path.join(
            cfg.session_dir_root, f"session_{ts}_{os.getpid()}_{_uuid.uuid4().hex[:6]}"
        )
        os.makedirs(os.path.join(self.session_dir, "logs"), exist_ok=True)

        self.gcs_server: GcsServer | None = None
        if head:
            self.gcs_server = GcsServer()
            self.gcs_address = self.gcs_server.address
        else:
            assert gcs_address is not None
            self.gcs_address = tuple(gcs_address)

        node_resources = dict(resources or {})
        node_resources.setdefault("CPU", num_cpus if num_cpus is not None else (os.cpu_count() or 1))
        tpus = num_tpus if num_tpus is not None else detect_tpu_chips()
        if tpus:
            node_resources.setdefault("TPU", tpus)
        node_labels = dict(labels or {})
        node_labels.update(detect_tpu_labels())

        self.raylet = Raylet(
            self.gcs_address,
            self.session_dir,
            resources=node_resources,
            labels=node_labels,
            object_store_memory=object_store_memory,
        )
        self.node_id = self.raylet.node_id

    def stop(self):
        self.raylet.stop()
        if self.gcs_server is not None:
            self.gcs_server.stop()
