"""Tier-1 dispatch-path smoke (microbench.py --smoke).

Runs the sync/async task, actor-call, and 1 MiB object-plane loops at tiny
counts (CPU-only, <30 s on an unloaded box) in a subprocess, so breakage of
the dispatch stack fails pytest here instead of only surfacing at the next
bench round.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_microbench_smoke(tmp_path):
    out = tmp_path / "smoke.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "microbench.py"), "--smoke", "--out", str(out)],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,  # generous for loaded CI boxes; ~5 s unloaded
    )
    assert proc.returncode == 0, (
        f"microbench --smoke failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for key in (
        "task_sync_per_s",
        "task_async100_per_s",
        "actor_call_sync_per_s",
        "actor_call_async100_per_s",
        "put_1mib_per_s",
        "putget_1mib_per_s",
    ):
        assert data.get(key, 0) > 0, f"{key} missing/zero in smoke artifact: {data}"


def test_transfer_smoke(tmp_path):
    """<30s --transfer --quick pass: raw-vs-msgpack push A/B, pull striping
    over the modeled per-source link, cut-through broadcast, and the
    dispatch-plane guards all produce nonzero numbers. Perf certification
    lives in the committed TRANSFER_r10.json (full shapes); this exists so
    transfer-plane breakage fails pytest instead of the next bench round."""
    out = tmp_path / "transfer.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--transfer",
            "--quick",
            "--round",
            "10",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert proc.returncode == 0, (
        f"microbench --transfer failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    assert not [k for k in data if k.endswith("_error")], data
    for key in (
        "push_raw_mib_per_s",
        "push_msgpack_mib_per_s",
        "pull_1replica_mib_per_s",
        "pull_2replica_mib_per_s",
        "broadcast_aggregate_mib_per_s",
        "putget_1mib_per_s",
        "shuffle_push_rows_per_s",
    ):
        assert data.get(key, 0) > 0, f"{key} missing/zero in transfer artifact: {data}"
    # The negotiated default must actually BE the raw path (a silent
    # fallback to msgpack everywhere would still produce numbers).
    assert data.get("transfer_chunks_raw", 0) > 0, data


def test_serve_llm_smoke(tmp_path):
    """<30s --serve --quick pass (ISSUE 11): the closed-loop generator runs
    against the serve.llm engine and produces nonzero TTFT/tokens-per-second
    numbers with prefix-cache hits. This exists so engine/scheduler breakage
    fails pytest instead of the next bench round; speed is the benchmark's
    (``benchmarks/run.py``), on the chip."""
    out = tmp_path / "servebench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--serve",
            "--quick",
            "--round",
            "11",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert proc.returncode == 0, (
        f"microbench --serve failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for key in (
        "serve_continuous_tokens_per_s",
        "serve_continuous_ttft_p99_ms",
        "serve_continuous_tpot_mean_ms",
    ):
        assert data.get(key, 0), f"{key} missing/zero in serve artifact: {data}"
    # The shared system prompt must actually ride the prefix cache.
    assert data.get("serve_continuous_prefix_hit_blocks", 0) > 0, data


def test_recorder_overhead_smoke(tmp_path):
    """<30s --recorder-overhead --quick pass: the always-on observability
    plane (flight recorder + 1-in-64 hop sampling) A/Bs against itself in
    one cluster and stays under a lenient bound. The committed artifact
    (OBSBENCH_r8.json, 150 pairs) records ~2%; the bound here is loose
    because this 1-core CI box shows +-10% single-pair noise and the quick
    pass only runs 8 pairs — it exists to catch an accidental O(task)
    instrumentation blowup (e.g. a per-task lock or RPC), not to re-certify
    the 3% acceptance number."""
    out = tmp_path / "obsbench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--recorder-overhead",
            "--quick",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert proc.returncode == 0, (
        f"microbench --recorder-overhead failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    assert data.get("recorder_on_task_sync_per_s", 0) > 0
    assert data.get("recorder_off_task_sync_per_s", 0) > 0
    assert len(data.get("recorder_pair_ratios", [])) >= 4
    assert data["recorder_overhead_pct"] < 25.0, data


def test_microbench_pipeline_smoke(tmp_path):
    """<60s --pipeline --quick pass (ISSUE 12): all four arms (spmd
    pipeline_apply, classic device-dispatch, classic host, MPMD compiled)
    produce throughput numbers at M=4, the MPMD outputs are bit-exact vs
    pipeline_apply, and the steady-state evidence holds — 0 raylet RPCs
    per iteration, 0 host-store activation objects, 0 host-fallback
    transfers (deterministic counters, not timing). Perf certification
    (>=2x vs classic dispatch, bubble at M in {4,16}) lives in the
    committed PIPEBENCH_r12.json — the quick arms are too short/noisy to
    re-certify ratios."""
    out = tmp_path / "pipebench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--pipeline",
            "--quick",
            "--round",
            "12",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=360,
    )
    assert proc.returncode == 0, (
        f"microbench --pipeline failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for key in (
        "pipeline_spmd_m4_iter_per_s",
        "pipeline_classic_m4_iter_per_s",
        "pipeline_classic_host_m4_iter_per_s",
        "pipeline_mpmd_m4_iter_per_s",
    ):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    assert data["pipeline_parity_bitexact"] is True, data
    assert data["pipeline_mpmd_m4_raylet_rpcs_per_iter"] == 0, data
    assert data["pipeline_mpmd_m4_store_objects_delta"] == 0, data
    assert data["pipeline_mpmd_m4_host_transfers_delta"] == 0, data
    assert data["pipeline_mpmd_m4_chan_sends"] > 0, data


def test_microbench_device_objects_smoke(tmp_path):
    """<30s device-object plane case (microbench.py --device-objects
    --quick): host and device paths both produce throughput numbers, and
    the zero-copy evidence holds — the same-process device loop adds ZERO
    objects to the node store (deterministic counter, not timing) while
    every iteration resolves as a local (live-array) transfer."""
    out = tmp_path / "devbench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--device-objects",
            "--quick",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert proc.returncode == 0, (
        f"microbench --device-objects failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for key in (
        "host_putget_1mib_per_s",
        "devobj_putget_1mib_per_s",
        "host_putget_32mib_per_s",
        "devobj_putget_32mib_per_s",
        "handoff_host_1mib_per_s",
        "handoff_devobj_1mib_per_s",
    ):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    # Zero host-shm copies of the payload on the same-process device path
    # (<= 0: the preceding host loop's async frees may still be draining).
    assert data["devobj_putget_1mib_store_objects_delta"] <= 0, data
    assert data["devobj_putget_32mib_store_objects_delta"] <= 0, data
    assert data["devobj_putget_1mib_local_transfers"] > 0, data


def test_microbench_collective_smoke(tmp_path):
    """<60s --collective --quick pass (ISSUE 15): both weight-sync arms
    (K-serial-unicast baseline, group broadcast) produce latency/throughput
    numbers at K=2, the device path's zero-host-store evidence holds
    (deterministic counters), residents drain after every sync, and the
    end-to-end Podracer IMPALA rows exist with every measured iteration's
    sync riding the broadcast plane. Perf certification (>=2x aggregate at
    K=8) lives in the committed COLLBENCH_r15.json — quick arms are too
    short/noisy to re-certify ratios."""
    out = tmp_path / "collbench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--collective",
            "--quick",
            "--round",
            "15",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=360,
    )
    assert proc.returncode == 0, (
        f"microbench --collective failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for key in (
        "wsync_serial_k2_s",
        "wsync_broadcast_k2_s",
        "wsync_serial_k2_mib_per_s",
        "wsync_broadcast_k2_mib_per_s",
        "podracer_host_iters_per_s",
        "podracer_device_broadcast_iters_per_s",
    ):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    # Device path: zero host-store copies of the payload, residents drained.
    assert data["wsync_broadcast_k2_store_objects_delta"] == 0, data
    assert data["wsync_k2_residents_after"] == 0, data
    # Every measured Podracer iteration's sync rode the broadcast plane.
    assert data["podracer_device_broadcasts"] >= 2, data
    # ISSUE 16 relay-tree arm: mid-tree members actually forwarded payload,
    # nothing touched the host store, and the allreduce oracle held
    # bit-exact (deterministic counters — ratio certification lives in the
    # committed COLLBENCH_r16.json full sweep).
    for key in ("relay_tree_k3_s", "relay_flat_k3_s", "allreduce_tree_k3_s"):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    assert data["relay_k3_relay_forwards"] > 0, data
    assert data["relay_k3_store_objects_delta"] == 0, data
    assert data["allreduce_k3_bit_exact"] == 1, data
    # ISSUE 20 reducescatter verb: tree and ring arms both produced rows,
    # every rank's shard matched the float32 oracle bit-exact, and the
    # tree arm's shards rode the direct mailboxes (scatter_bytes moved).
    for key in ("reducescatter_tree_k3_s", "reducescatter_ring_k3_s"):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    assert data["reducescatter_k3_bit_exact"] == 1, data
    assert data["reducescatter_k3_scatter_bytes"] > 0, data


def test_microbench_resize_smoke(tmp_path):
    """<90s --collective --resize --quick pass (ISSUE 17): IMPALA on the
    device-broadcast plane through a scripted 2→4→2 sampler resize. The
    suite's inline oracle is the real assertion — after the first
    post-resize iteration every measured weight sync rides the broadcast
    plane (fleet-wide host-sync fallback delta == 0 in every phase, which
    a failed roster join/evict would break). Full-shape 8→16→8 evidence
    lives in the committed RESIZEBENCH_r17.json."""
    out = tmp_path / "resizebench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--collective",
            "--resize",
            "--quick",
            "--round",
            "17",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=360,
    )
    assert proc.returncode == 0, (
        f"microbench --collective --resize failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    assert data["resize_schedule"] == [2, 4, 2], data
    for phase, n in enumerate(data["resize_schedule"]):
        assert data.get(f"resize_p{phase}_n{n}_iters_per_s", 0) > 0, data
        # Plane syncs cover the whole fleet every measured iteration...
        assert data[f"resize_p{phase}_n{n}_plane_syncs"] >= n * 2, data
        # ...and ZERO host-sync fallbacks after the first post-resize iter.
        assert data[f"resize_p{phase}_n{n}_host_fallbacks"] == 0, data
    # Grow and shrink both happened and were timed.
    assert data.get("resize_p1_to4_s", 0) > 0, data
    assert data.get("resize_p2_to2_s", 0) > 0, data
    # After the final shrink the roster is back to learner + 2 samplers.
    assert data["resize_final_roster_ranks"] == [0, 1, 2], data


@pytest.mark.slow
def test_collective_k8_sweep(tmp_path):
    """Full-shape K in {2,4,8} sweep (slow): the broadcast arm must beat
    the K-serial-unicast arm at K=8. The committed COLLBENCH_r15.json
    certifies >=2x on an idle box; this bound is looser because shared CI
    boxes inflate the (concurrency-sensitive) broadcast arm more than the
    serial one."""
    out = tmp_path / "collbench_full.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--collective",
            "--round",
            "15",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"microbench --collective failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for k in (2, 4, 8):
        assert data.get(f"wsync_broadcast_k{k}_mib_per_s", 0) > 0, data
        assert data[f"wsync_broadcast_k{k}_store_objects_delta"] == 0, data
        assert data[f"wsync_k{k}_residents_after"] == 0, data
    assert data["wsync_speedup_k8"] > 1.2, data
    # ISSUE 16: under the modeled per-process egress link the relay tree
    # must beat the flat fan-out at K=8 and the gap must WIDEN with K
    # (root egress is O(log K) vs O(K)); the allreduce oracle stays
    # bit-exact at every K.
    for k in (4, 8):
        assert data.get(f"relay_tree_k{k}_agg_mib_per_s", 0) > 0, data
        assert data[f"relay_k{k}_store_objects_delta"] == 0, data
        assert data[f"relay_k{k}_relay_forwards"] > 0, data
        assert data[f"allreduce_k{k}_bit_exact"] == 1, data
        assert data.get(f"reducescatter_tree_k{k}_agg_mib_per_s", 0) > 0, data
        assert data[f"reducescatter_k{k}_bit_exact"] == 1, data
        assert data[f"reducescatter_k{k}_scatter_bytes"] > 0, data
    assert data["relay_tree_speedup_k8"] > 1.2, data
    assert data["relay_tree_speedup_k8"] > data["relay_tree_speedup_k4"], data
    assert (
        data["relay_tree_k8_root_egress_frac"] < data["relay_tree_k4_root_egress_frac"]
    ), data


def test_microbench_sim_smoke(tmp_path):
    """--sim --quick pass (ISSUE 19): the control-plane scale harness boots
    64/128-shell sim clusters in both heartbeat arms and produces the full
    evidence shape — delta arm with ZERO steady-state view rows vs the
    legacy full-view arm's per-node byte tax, node-death index vs scan,
    locality arms with 100% holder hits and a no-locality baseline, the
    bounded task-event ring with an exact dropped count, and a passing SLO
    scorecard. Scale certification (512/1000 shells, sub-quadratic curve)
    lives in the committed SIMBENCH_r19.json — the quick arms only prove
    the machinery."""
    out = tmp_path / "simbench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--sim",
            "--quick",
            "--round",
            "19",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=360,
    )
    assert proc.returncode == 0, (
        f"microbench --sim failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    sweep = data["sim_sweep"]
    for arm in ("n64_delta", "n64_legacy", "n128_delta", "n128_legacy"):
        assert sweep[arm]["tasks_per_s"] > 0, sweep
        assert sweep[arm]["placement_p99_ms"] > 0, sweep
    # The fan-in fix, as counters: delta arm serves ZERO full replies and
    # ZERO steady-state view rows; the legacy arm pays O(N) rows per reply.
    for n in (64, 128):
        assert sweep[f"n{n}_delta"]["hb_full_replies"] == 0, sweep
        assert sweep[f"n{n}_delta"]["hb_view_rows_per_interval"] == 0, sweep
        assert sweep[f"n{n}_legacy"]["hb_view_bytes_per_node_per_interval"] > 0, sweep
    # Per-node heartbeat bytes GROW with N on the legacy arm (the quadratic
    # signature) — the delta arm's stay flat at zero.
    assert (
        sweep["n128_legacy"]["hb_view_bytes_per_node_per_interval"]
        > sweep["n64_legacy"]["hb_view_bytes_per_node_per_interval"]
    ), sweep
    # Node-death via the per-node location index beats the full-table scan.
    death = data["sim_node_death"]
    assert death["index"]["victim_rows"] == death["scan"]["victim_rows"] > 0, death
    assert death["index"]["on_node_death_ms"] < death["scan"]["on_node_death_ms"], death
    # Locality arm pins every ref-arg task to its holder, flight-evidenced;
    # the no-locality arm is the measured zero baseline.
    loc = data["sim_locality"]
    assert loc["locality"]["holder_hit_frac"] == 1.0, loc
    assert loc["locality"]["locality_hit_events"] > 0, loc
    assert loc["no_locality"]["holder_hits"] == 0, loc
    # Event flood: ring bounded, drops counted exactly.
    ev = data["sim_task_events"]
    assert ev["ring_size_after"] == ev["ring_maxlen"], ev
    assert ev["events_dropped_total"] == ev["events_sent"] - ev["ring_maxlen"], ev
    # Chaos cells all posted passing SLO verdicts.
    assert data["sim_slo_ok"] is True, data.get("sim_slo_scorecard")


def test_microbench_dag_smoke(tmp_path):
    """<30s classic-vs-compiled DAG case (microbench.py --dag --quick):
    both paths produce throughput numbers, and the compiled loop's
    control-plane evidence holds — 0 raylet RPCs and 0 new ObjectRefs per
    iteration (deterministic counters, not timing)."""
    out = tmp_path / "dagbench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--dag",
            "--quick",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,  # generous for loaded CI boxes; ~7 s unloaded
    )
    assert proc.returncode == 0, (
        f"microbench --dag failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    for key in ("dag_classic_per_s", "dag_compiled_per_s"):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    assert data["dag_compiled_raylet_rpcs_per_iter"] == 0
    assert data["dag_compiled_new_object_refs_per_iter"] == 0
    # Compiled stamps exist and contain no raylet stage.
    compiled_budget = data["dag_hop_budget"]["compiled"]
    assert compiled_budget["count"] > 0
    assert not any("raylet" in s for s in compiled_budget["stages_us"])


def test_serve_disagg_smoke(tmp_path):
    """--serve-disagg --quick pass (ISSUE 20): the disaggregated arm boots
    a real serve instance (2 prefill + 2 decode replicas), streams mixed
    long-prefill/short-decode load, and the machinery evidence holds on
    deterministic counters — every short stream rode a prefill->decode KV
    handoff with ZERO store objects minted, the warm-seeded cluster prefix
    row produced a cross-replica import hit, and every replica's KV pool
    drained back to full. The tiny quick model is dispatch-bound on one
    host CPU, so TTFT/throughput RATIOS are certified by the committed
    DISAGGBENCH_r20.json full sweep (compute-bound model), not here."""
    out = tmp_path / "disaggbench.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--serve-disagg",
            "--quick",
            "--round",
            "20",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=360,
    )
    assert proc.returncode == 0, (
        f"microbench --serve-disagg failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    # Both arms streamed real tokens.
    for key in ("mono_tokens_per_s", "disagg_tokens_per_s"):
        assert data.get(key, 0) > 0, f"{key} missing/zero: {data}"
    # Monolithic arm never handed off; disaggregated arm always did.
    assert data["mono_kv_leak_blocks"] == 0, data
    assert data["disagg_handoffs"] > 0, data
    assert data["disagg_handoff_failed"] == 0, data
    # Zero raylet-store traffic on the handoff path (sealed device objects
    # over direct mailboxes, not plasma).
    assert data["disagg_store_objects_delta"] == 0, data
    assert data["mono_store_objects_delta"] == 0, data
    # Cluster prefix tier: the warm phase's shared system prompt produced
    # at least one cross-replica import instead of a recompute.
    assert data["disagg_prefix_import_hits"] > 0, data
    # KV pools fully restored once idle (free + cached == total).
    assert data["disagg_kv_leak_blocks"] == 0, data
    # Flight evidence rode along (codes 50/51).
    assert data["disagg_handoff_flight_events"] > 0, data
    assert data["disagg_prefix_import_flight_events"] > 0, data


@pytest.mark.slow
def test_serve_disagg_full_sweep(tmp_path):
    """Full compute-bound sweep (slow): disaggregation must materially cut
    short-stream p99 TTFT under mixed load at an EQUAL replica budget
    without giving up aggregate throughput. The committed
    DISAGGBENCH_r20.json certifies -69.9% p99 TTFT and 1.21x tokens on an
    idle box; these bounds are looser because shared CI boxes inflate the
    (latency-sensitive) closed-loop arms unevenly."""
    out = tmp_path / "disaggbench_full.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="0")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "microbench.py"),
            "--serve-disagg",
            "--round",
            "20",
            "--out",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"microbench --serve-disagg failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    data = json.loads(out.read_text())
    assert data["disagg_short_ttft_p99_ms"] < data["mono_short_ttft_p99_ms"], data
    assert data["disagg_short_ttft_p99_reduction_pct"] > 20, data
    assert data["disagg_tokens_vs_mono"] >= 0.9, data
    assert data["disagg_handoff_failed"] == 0, data
    assert data["disagg_prefix_import_hits"] > 0, data
    assert data["disagg_store_objects_delta"] == 0, data
    assert data["disagg_kv_leak_blocks"] == 0, data
