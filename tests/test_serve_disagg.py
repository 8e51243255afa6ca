"""Prefill/decode disaggregation + cluster KV prefix tier (ISSUE 20).

Two planes under test, sharing ONE module-scoped cluster (tier-1 budget):

- **Handoff oracles, end to end over a REAL serve instance** (controller +
  proxy + 1 prefill replica + 2 decode replicas): a prompt prefilled on
  pool A and decoded on pool B must yield BYTE-IDENTICAL tokens vs a
  single-replica (monolithic engine) run — greedy and seeded sampling —
  and must stay byte-identical when a seeded plan SIGKILLs the serving
  decode replica mid-stream (the PR 14 migration path re-prefills and
  teacher-forces on the surviving decode replica).

- **Cluster prefix tier lifecycle, on driver-attached engines** (the
  driver's core worker is the holder/importer — same sealing, registry
  rows, typed-miss and retraction code paths the replicas run; the
  cross-PROCESS import leg is exercised by the serve handoff oracles above
  and the --serve-disagg bench smoke): publish→import bit-exactness,
  sealed-copy immunity to holder pool churn (import-while-evicting can
  serve but never hand a torn block), typed miss + stale-row retraction
  when the payload died under the row (the holder-death story: importers
  garbage-collect rows for corpses), LRU-cap retraction, and GCS KV back
  to baseline after engine shutdown.
"""

import json
import threading
import time
import urllib.request
import zlib

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve._private.common import CONTROLLER_NAME, PREFIX_HINT_HEADER

MODEL = dict(
    vocab_size=64,
    d_model=32,
    n_layers=1,
    n_heads=2,
    n_kv_heads=2,
    d_ff=48,
    max_seq_len=64,
    dtype="float32",
    remat=False,
)
ENGINE = dict(num_slots=4, block_size=4, max_model_len=64, prefill_chunk=4)
SYSTEM = list(range(3, 3 + 16))  # 4 full blocks shared across prompts


@pytest.fixture(scope="module")
def disagg_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=6, object_store_memory=96 * 1024 * 1024)
        cluster.connect()
        cluster.wait_for_nodes()
        serve.start()
        yield cluster
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


@pytest.fixture(scope="module")
def disagg_app(disagg_cluster):
    from ray_tpu.serve.llm import disaggregated_llm_app

    serve.run(
        disaggregated_llm_app(
            MODEL,
            dict(ENGINE),
            name="llm",
            prefill_replicas=1,
            decode_replicas=2,
            cluster_prefix=True,
        )
    )
    return disagg_cluster


def _oracle(prompt, n, **sampling):
    """Uninterrupted single-engine (monolithic) reference run with the same
    seed-deterministic params the replicas build (init_seed=0)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    kw = dict(MODEL)
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    cfg = TransformerConfig(**kw)
    eng = LLMEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, **ENGINE)
    try:
        return eng.submit(prompt, max_new_tokens=n, **sampling).result(120)
    finally:
        eng.shutdown()


def _replicas(dep):
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    table = ray_tpu.get(controller.get_routing_table.remote(-2, 0.1))["table"]
    return [r["actor_name"] for r in table.get(dep, {}).get("replicas", [])]


def _replica_stats(dep):
    out = []
    for name in _replicas(dep):
        try:
            out.append(
                ray_tpu.get(
                    ray_tpu.get_actor(name).handle_request.remote(
                        "get_stats", (), {}
                    ),
                    timeout=15,
                )
            )
        except Exception:
            pass
    return out


def _stream_sse(url, body, headers=None, timeout=240):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), headers=headers or {}
    )
    resp = urllib.request.urlopen(req, timeout=timeout)
    toks, buf = [], b""
    while True:
        chunk = resp.read(64)
        if not chunk:
            return toks, False
        buf += chunk
        while b"\n\n" in buf:
            event, buf = buf.split(b"\n\n", 1)
            if not event.startswith(b"data: "):
                continue
            payload = event[6:]
            if payload == b"[DONE]":
                return toks, True
            toks.append(json.loads(payload)["token"])


def _flight_events(cluster, kind, since_wall):
    from ray_tpu._private.rpc import EventLoopThread

    resp = EventLoopThread.get().run(cluster.nodes[0].rpc_debug_dump({}), timeout=15)
    return [
        ev
        for proc in resp.get("processes", [])
        for ev in proc.get("events", [])
        if ev.get("type") == kind and ev.get("ts", 0) >= since_wall - 2.0
    ]


def _handoffs_failed():
    return sum(
        s.get("handoff_failed", 0) for dep in ("llm", "llm--prefill") for s in _replica_stats(dep)
    )


def _store_objects():
    from ray_tpu._private import worker_context

    return worker_context.get_core_worker().raylet.call("get_state")["store"]["num_objects"]


def _wait_kv_restored(deps=("llm", "llm--prefill")):
    """Leak oracle: every live replica's KV pool back to full once idle."""
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        stats = [s for dep in deps for s in _replica_stats(dep)]
        if stats and all(
            s["free_blocks"] + s["cached_blocks"] == s["num_blocks"] for s in stats
        ):
            return
        time.sleep(0.25)
    pytest.fail(f"replicas leaked KV blocks: {stats}")


def _run_handoff_oracle(cluster, prompt, n, sampling, kill=False):
    """POST one stream through the disaggregated app; the client's tokens
    must be byte-identical to the monolithic oracle, and the output must
    provably have ridden a prefill→decode handoff (counter delta, flight
    event) — with an optional seeded mid-stream SIGKILL of the serving
    decode replica."""
    from ray_tpu.serve.llm import prefix_route_hint

    expect = _oracle(prompt, n, **sampling)
    host, port = serve.http_address()
    url = f"http://{host}:{port}/llm"
    t_wall0 = time.time()
    handoffs0 = sum(s.get("handoffs", 0) for s in _replica_stats("llm"))
    exports0 = sum(s.get("handoff_exports", 0) for s in _replica_stats("llm--prefill"))
    failed0 = _handoffs_failed()
    store0 = _store_objects()
    hint = prefix_route_hint(prompt, ENGINE["block_size"])
    assert hint
    if kill:
        # A previous kill's replacement may still be booting.
        deadline = time.monotonic() + 180
        actors = _replicas("llm")
        while len(actors) < 2 and time.monotonic() < deadline:
            time.sleep(0.25)
            actors = _replicas("llm")
        assert len(actors) == 2, actors
        # The prefix hint pins the decode-pool pick, so the victim is known
        # BEFORE the request and the kill point (2nd actor-call response:
        # the accept + first stream-chunk pump) is seeded and replayable.
        victim = actors[zlib.crc32(hint.encode()) % len(actors)]
        assert cluster.install_plan_in_actor(
            victim,
            {"rules": [{"kind": "kill", "method": ["actor_call"],
                        "side": "resp", "after": 2, "times": 1}]},
            seed=13,
        )
    toks, done = _stream_sse(
        url,
        dict(tokens=prompt, max_new_tokens=n, **sampling),
        headers={PREFIX_HINT_HEADER: hint},
    )
    assert done, "stream ended without [DONE]"
    assert toks == expect, (toks, expect)
    # The tokens came through the pools, not a monolithic fallback: the
    # prefill pool sealed+exported and a decode replica imported.
    assert (
        sum(s.get("handoff_exports", 0) for s in _replica_stats("llm--prefill"))
        > exports0
    )
    if not kill:
        assert sum(s.get("handoffs", 0) for s in _replica_stats("llm")) > handoffs0
        assert _handoffs_failed() == failed0
        # The descriptor rode an actor call and the KV payload the direct
        # mailboxes: the hand-off minted nothing in the node's store (the
        # proxy frees its stream buffers asynchronously: bounded settle).
        _wait(lambda: _store_objects() <= store0, msg="store objects back to baseline")
    assert _flight_events(cluster, "llm_kv_handoff", t_wall0), "no handoff recorded"
    if kill:
        assert _flight_events(cluster, "llm_migrate", t_wall0), "no migration"
        assert _flight_events(cluster, "chaos_kill", t_wall0), "no kill recorded"
    _wait_kv_restored()


def test_handoff_byte_identical_greedy(disagg_app):
    """THE tentpole oracle: prefilled on pool A, decoded on pool B, tokens
    byte-identical to a single-replica run (greedy)."""
    _run_handoff_oracle(
        disagg_app, prompt=[3, 1, 4, 1, 5, 9, 2, 6], n=24, sampling={}
    )


def test_handoff_byte_identical_seeded_sampling(disagg_app):
    """Sampled arm: the counter-based per-request RNG makes the handed-off
    continuation bit-identical too (tok0 drawn at the prefill pool, the
    rest at the decode pool, same stream as one engine drawing all 24)."""
    _run_handoff_oracle(
        disagg_app,
        prompt=[2, 7, 1, 8, 2, 8, 1, 8],
        n=24,
        sampling=dict(temperature=0.9, top_k=16, seed=11),
    )


def test_handoff_decode_kill_midstream_greedy(disagg_app):
    """A seeded plan SIGKILLs the serving DECODE replica mid-stream: the
    proxy migrates to the surviving decode replica (re-prefill + teacher-
    forced resume — the sealed import died with the victim) and the client
    still sees the byte-exact uninterrupted sequence."""
    _run_handoff_oracle(
        disagg_app, prompt=[1, 6, 1, 8, 0, 3, 3, 9], n=24, sampling={}, kill=True
    )


@pytest.mark.slow
def test_handoff_decode_kill_midstream_seeded_sampling(disagg_app):
    """Kill arm under seeded sampling: migration + handoff + RNG counters
    compose — still byte-identical."""
    _run_handoff_oracle(
        disagg_app,
        prompt=[2, 2, 5, 3, 0, 6, 1, 7],
        n=24,
        sampling=dict(temperature=0.8, top_k=8, seed=5),
        kill=True,
    )


# ---------------------------------------------------------------------------
# cluster prefix tier lifecycle (driver-attached engines)
# ---------------------------------------------------------------------------


def _mk_engine(**overrides):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.llm import LLMEngine

    kw = dict(MODEL)
    kw["dtype"] = jnp.dtype(kw["dtype"]).type
    cfg = TransformerConfig(**kw)
    return LLMEngine(
        init_params(jax.random.PRNGKey(0), cfg), cfg, **dict(ENGINE, **overrides)
    )


def _cw():
    from ray_tpu._private import worker_context

    return worker_context.get_core_worker()


def _row(h):
    from ray_tpu.serve.llm import kv_transfer

    return kv_transfer.lookup_prefix_row(_cw(), h)


def _wait(pred, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    pytest.fail(f"timed out waiting for {msg}")


def test_prefix_import_bit_identical_and_local_seed(disagg_cluster):
    """Engine A publishes the shared prefix; engine B's registry probe
    imports it and B's output is byte-identical to an engine that computed
    everything itself. The import also seeds B's LOCAL prefix cache, so
    B's next same-prefix prompt never probes the registry again."""
    from ray_tpu.serve.llm.engine import block_hashes

    a = _mk_engine(cluster_prefix=True)
    b = _mk_engine(cluster_prefix=True)
    try:
        expect = _oracle(SYSTEM + [33, 35, 37, 39, 41, 43, 45, 47], 6)
        a.submit(SYSTEM + [20, 22, 24, 26, 28, 30, 32, 34], max_new_tokens=4).result(
            120
        )
        # Rows are fire-and-forget: wait for the shared depth-4 row to land.
        shared = block_hashes(SYSTEM, ENGINE["block_size"])[-1]
        _wait(lambda: _row(shared) is not None, msg="published prefix row")
        out = b.submit(
            SYSTEM + [33, 35, 37, 39, 41, 43, 45, 47], max_new_tokens=6
        ).result(120)
        assert out == expect, (out, expect)
        st = b.stats()
        assert st["prefix_import_hits"] == 1, st
        assert st["prefix_import_errors"] == 0, st
        from ray_tpu._private import flight_recorder

        events = (flight_recorder.dump() or {}).get("events", [])
        assert any(e["type"] == "llm_prefix_import" for e in events), "import left no flight event"
        # Second same-prefix prompt: the import registered the blocks in
        # B's local cache, so the probe short-circuits (hits stay at 1)
        # and the output is still oracle-exact.
        expect2 = _oracle(SYSTEM + [49, 51, 53, 55], 4)
        out2 = b.submit(SYSTEM + [49, 51, 53, 55], max_new_tokens=4).result(120)
        assert out2 == expect2
        assert b.stats()["prefix_import_hits"] == 1, b.stats()
    finally:
        a.shutdown()
        b.shutdown()


def test_sealed_copy_survives_holder_pool_churn(disagg_cluster):
    """Import-while-evicting, the serve side: the published payload is a
    SEALED COPY, so the holder recycling every pool block it was built
    from (12 distinct prompts churning a 64-block pool) cannot tear a
    later import — B still gets byte-exact tokens."""
    from ray_tpu.serve.llm.engine import block_hashes

    a = _mk_engine(cluster_prefix=True)
    b = _mk_engine(cluster_prefix=True)
    try:
        a.submit(SYSTEM + [2, 4, 6, 8], max_new_tokens=2).result(120)
        shared = block_hashes(SYSTEM, ENGINE["block_size"])[-1]
        _wait(lambda: _row(shared) is not None, msg="published prefix row")
        # Churn: distinct UNSHARED prompts overwrite the holder's pool.
        rng = np.random.default_rng(9)
        for _ in range(12):
            p = rng.integers(32, 64, 32).tolist()
            a.submit(p, max_new_tokens=2).result(120)
        expect = _oracle(SYSTEM + [11, 13, 15, 17], 6)
        out = b.submit(SYSTEM + [11, 13, 15, 17], max_new_tokens=6).result(120)
        assert out == expect, (out, expect)
        assert b.stats()["prefix_import_hits"] == 1, b.stats()
    finally:
        a.shutdown()
        b.shutdown()


def test_freed_payload_is_typed_miss_and_importer_retracts(disagg_cluster):
    """Import racing eviction/holder death, the miss side: the payload
    died under a still-present row. The importer gets the TYPED miss
    (DeviceObjectLostError, never a torn block), falls back to recompute
    (output still byte-exact), and retracts the stale row so the next
    prober skips the corpse — the holder-death garbage-collection story."""
    from ray_tpu.serve.llm.engine import block_hashes

    a = _mk_engine(cluster_prefix=True)
    b = _mk_engine(cluster_prefix=True)
    try:
        prompt_a = SYSTEM + [20, 22, 24, 26, 28, 30, 32, 34]
        a.submit(prompt_a, max_new_tokens=2).result(120)
        deep = block_hashes(prompt_a, ENGINE["block_size"])[4]
        shared = block_hashes(SYSTEM, ENGINE["block_size"])[-1]
        _wait(lambda: _row(deep) is not None, msg="published prefix row")
        # Kill the payload OUT FROM UNDER the rows (what eviction racing a
        # lookup, or a dead holder, looks like to an importer).
        oid = _row(shared)["oid"]
        _cw()._device_manager().free(oid)
        expect = _oracle(SYSTEM + [33, 35, 37, 39], 6)
        out = b.submit(SYSTEM + [33, 35, 37, 39], max_new_tokens=6).result(120)
        assert out == expect, (out, expect)
        st = b.stats()
        assert st["prefix_import_errors"] == 1, st
        assert st["prefix_import_hits"] == 0, st
        # The stale row B probed is gone. B republishes the prefix it just
        # recomputed (it is a cluster_prefix holder too), so the key may be
        # occupied again — the invariant is that no row points at the
        # corpse, not that the key is empty (read-check-delete semantics).
        _wait(
            lambda: (_row(shared) or {}).get("oid") != oid,
            msg="stale row retraction",
        )
    finally:
        a.shutdown()
        b.shutdown()


def test_lru_cap_retracts_evicted_rows(disagg_cluster):
    """cluster_prefix_max=1: publishing a second prefix evicts the first
    sealed payload AND retracts its registry rows; the survivor's rows
    stay."""
    from ray_tpu.serve.llm.engine import block_hashes

    a = _mk_engine(cluster_prefix=True, cluster_prefix_max=1)
    try:
        p1 = [10] * 4 + list(range(36, 48))
        p2 = [11] * 4 + list(range(36, 48))
        a.submit(p1, max_new_tokens=2).result(120)
        h1 = block_hashes(p1, ENGINE["block_size"])[-2]
        _wait(lambda: _row(h1) is not None, msg="first prefix row")
        a.submit(p2, max_new_tokens=2).result(120)
        h2 = block_hashes(p2, ENGINE["block_size"])[-2]
        _wait(lambda: _row(h2) is not None, msg="second prefix row")
        _wait(lambda: _row(h1) is None, msg="evicted prefix row retraction")
        assert a.stats()["published_prefixes"] == 1, a.stats()
    finally:
        a.shutdown()


def test_gcs_rows_return_to_baseline_after_shutdown(disagg_cluster):
    """Engine shutdown retracts every row it published and frees the
    sealed payloads: the GCS KV's llmprefix/ keyspace returns to its
    pre-engine baseline (no abandoned rows for importers to chase)."""
    from ray_tpu.serve.llm.kv_transfer import PREFIX_ROW

    def row_count():
        got = _cw().gcs.call("kv_keys", {"prefix": PREFIX_ROW}, timeout=10)
        return len(got.get("keys", []))

    baseline = row_count()
    a = _mk_engine(cluster_prefix=True)
    b = _mk_engine(cluster_prefix=True)
    try:
        rng = np.random.default_rng(3)
        for eng in (a, b):
            for _ in range(2):
                eng.submit(
                    rng.integers(0, 64, 24).tolist(), max_new_tokens=2
                ).result(120)
        _wait(lambda: row_count() > baseline, msg="published rows")
    finally:
        a.shutdown()
        b.shutdown()
    _wait(
        lambda: row_count() <= baseline,
        msg="rows retracted on shutdown",
    )
