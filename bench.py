"""Headline benchmark.

Measures flagship-transformer training throughput through the full framework
path (JaxTrainer -> worker actor -> collective-plane mesh -> jitted train
step) against a pure-JAX loop in the same process. vs_baseline is the
framework/pure ratio — the BASELINE.md target is >= 0.90 (framework overhead
<= 10%); >1.0 is noise-level win.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

On a TPU host the worker claims the chip (the driver process never imports
jax — by design, see _private/node.py). It fails when it finds no chip; only
a caller that pinned JAX_PLATFORMS=cpu gets the scaled-down CPU config, under
a metric name that says so.
"""

from __future__ import annotations

import json
import os
import sys
import time


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.air import session
    from ray_tpu.models.transformer import TransformerConfig, init_params, make_train_step

    platform = jax.devices()[0].platform
    if platform != config["platform"]:
        raise RuntimeError(
            f"bench worker is on platform {platform!r}, expected {config['platform']!r}"
        )
    on_tpu = platform == "tpu"
    # A/B knobs (defaults = the measured-best config; see PERF_NOTES.md):
    #   BENCH_FUSED=0        unfused LM loss (materialized logits)
    #   BENCH_UNROLL=N       layer-scan unroll factor
    #   BENCH_LAG=N          framework-loop metrics lag depth
    #   BENCH_NO_ASYNC_COPY=1  skip per-step copy_to_host_async
    #   BENCH_STEPS=N        timed steps
    # Interleaved A/B (4 reps each, r4): unfused 90.0k vs fused 87.6k tok/s —
    # at bench shapes the backward's head-matmul recompute (+2·N·D·V FLOPs)
    # outweighs the saved logits bandwidth. fused_loss remains the memory
    # knob for vocab/seq scales where the [N, V] tensor doesn't fit.
    fused = os.environ.get("BENCH_FUSED", "0") != "0"
    unroll = int(os.environ.get("BENCH_UNROLL", "8"))
    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32000,
            d_model=1024,
            n_layers=8,
            # head_dim = 128 (the MXU-native width, Llama-style). Identical
            # params/FLOPs to 16 heads of 64, but attention matmuls contract
            # over a full 128-lane tile: interleaved A/B measured 95.5k ->
            # 113.0k tok/s (+18%) switching head_dim 64 -> 128.
            n_heads=8,
            n_kv_heads=8,
            d_ff=2816,
            max_seq_len=1024,
            dtype=jnp.bfloat16,
            remat=False,
            # Single chip, no pp: full unroll lets XLA schedule across layer
            # boundaries (+12% measured on v5e — see TransformerConfig).
            scan_unroll=unroll,
            fused_loss=fused,
        )
        # batch 12: interleaved A/B (r5) measured 124.7k tok/s vs 121.4k at
        # batch 8 and 123.4k at 16 on the same chip — the MFU sweet spot for
        # these shapes.
        batch, seq, steps = (
            int(os.environ.get("BENCH_BATCH", "12")),
            1024,
            int(os.environ.get("BENCH_STEPS", "192")),
        )
    else:
        cfg = TransformerConfig(
            vocab_size=1024,
            d_model=128,
            n_layers=2,
            n_heads=4,
            n_kv_heads=4,
            d_ff=256,
            max_seq_len=128,
            dtype=jnp.float32,
            remat=False,
            fused_loss=fused,
            scan_unroll=min(unroll, 2),
        )
        batch, seq, steps = 4, 128, int(os.environ.get("BENCH_STEPS", "10"))

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    batch_arr = {"tokens": tokens}

    # Warmup/compile. Timed regions end with float(loss): a host transfer
    # of the last step's loss cannot complete before the step has.
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, batch_arr)
    float(loss)

    # Measurement: the pure-JAX baseline (tight loop, no framework
    # interaction) and the framework path (same loop, losses reported
    # through the air session) run INTERLEAVED in ABBA-ordered chunks —
    # raw/fw, fw/raw, ... — and the ratio is summed-raw / summed-fw.
    # Sequential windows measured ±0.5-1% run-to-run drift, which landed
    # entirely in vs_baseline; alternating chunks cancels linear drift
    # exactly and halves the rest.
    # Each chunk ends with one synchronous host fetch (float(loss) for raw,
    # the logger's batch fetch for fw), so chunk-boundary drain cost is
    # symmetric.
    #
    # Framework logger shape: losses are batched ON DEVICE (one jnp.stack +
    # one async D2H copy per BENCH_LAG steps) and fetched one batch LATE
    # inside a chunk, so each copy has a full batch of steps to land before
    # it is read. Per-step Python cost is a list append. A per-step
    # synchronous float() would pay the device->host round trip every
    # iteration and throttle dispatch depth. Every loss is still reported,
    # in order — this is the shape of any well-written training metrics
    # logger, batched host syncs included.
    import collections

    import numpy as np

    # lag >= 1: a batch of 1 degenerates to the per-step async-copy logger.
    # Chunk default: half the steps (one ABBA pair of big windows). Each
    # chunk drain pays one synchronous D2H round trip, so fewer, bigger
    # windows keep measured tok/s honest to the steady state while ABBA
    # still cancels linear drift.
    lag = max(1, int(os.environ.get("BENCH_LAG", "16")))
    chunk = max(lag, int(os.environ.get("BENCH_CHUNK", str(max(lag, steps // 2)))))
    async_copy = os.environ.get("BENCH_NO_ASYNC_COPY", "0") != "1"
    rounds = max(2, steps // chunk)
    rounds += rounds % 2  # even round count: raw and fw lead equally often
    steps = rounds * chunk  # per loop

    def _flush(base, arr):
        for j, val in enumerate(np.asarray(arr)):
            session.report({"step": base + j, "loss": float(val)})

    # Precompile the stack/fetch shapes the logger uses (lag and the final
    # partial batch of a chunk) so no compile lands inside a timed window.
    for warm_n in {lag, chunk % lag or lag, 1}:
        np.asarray(jnp.stack([loss] * warm_n))

    def run_raw_chunk():
        nonlocal params, opt_state, loss
        t0 = time.perf_counter()
        for _ in range(chunk):
            params, opt_state, loss = step(params, opt_state, batch_arr)
        float(loss)
        return time.perf_counter() - t0

    fw_step = 0

    def run_fw_chunk():
        nonlocal params, opt_state, loss, fw_step
        tail: list = []
        inflight: collections.deque = collections.deque()
        t0 = time.perf_counter()
        for _ in range(chunk):
            params, opt_state, loss = step(params, opt_state, batch_arr)
            tail.append(loss)
            fw_step += 1
            if len(tail) == lag:
                stacked = jnp.stack(tail)
                tail = []
                if async_copy:
                    stacked.copy_to_host_async()
                inflight.append((fw_step - lag, stacked))
                if len(inflight) > 1:
                    _flush(*inflight.popleft())
        while inflight:
            _flush(*inflight.popleft())
        if tail:
            _flush(fw_step - len(tail), jnp.stack(tail))
        return time.perf_counter() - t0

    raw_s = fw_s = 0.0
    for r in range(rounds):
        if r % 2 == 0:
            raw_s += run_raw_chunk()
            fw_s += run_fw_chunk()
        else:
            fw_s += run_fw_chunk()
            raw_s += run_raw_chunk()

    tok = batch * seq * steps
    session.report(
        {
            "final": True,
            "tokens_per_sec_framework": tok / fw_s,
            "tokens_per_sec_raw": tok / raw_s,
            "ratio": raw_s / fw_s if fw_s > 0 else 0.0,
            "platform": platform,
            "n_params": n_params,
            "device_kind": jax.devices()[0].device_kind,
        }
    )


def main():
    import ray_tpu
    from ray_tpu._private.node import detect_tpu_chips, pinned_jax_platform
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer
    from ray_tpu.util.compile_cache import export_compile_cache_dir

    export_compile_cache_dir(__file__)
    cpu_pinned = pinned_jax_platform() == "cpu"
    n_tpus = 0 if cpu_pinned else detect_tpu_chips()
    if not cpu_pinned and n_tpus == 0:
        sys.exit(
            "bench.py: no TPU chip found (no /dev/accel<n> or /dev/vfio/<n>); "
            "pin JAX_PLATFORMS=cpu for the scaled-down CPU run"
        )

    ray_tpu.init(num_cpus=4, num_tpus=n_tpus)
    use_tpu = n_tpus > 0
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"platform": "tpu" if use_tpu else "cpu"},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=use_tpu, tpu_per_worker=1 if use_tpu else 0
        ),
        run_config=RunConfig(storage_path="/tmp/rtpu_bench"),
    )
    result = trainer.fit()
    m = result.metrics
    ray_tpu.shutdown()
    suffix = "_" + m["platform"]
    out = {
        "metric": "flagship_transformer_train_tokens_per_sec" + suffix,
        "value": round(m["tokens_per_sec_framework"], 1),
        "unit": "tokens/s",
        # 3 decimals = the measurement's honest precision: with ABBA
        # interleaving the framework/pure ratio's run-to-run spread is
        # ~±5e-4 (measured r5: 1.0001 / 0.9999 back-to-back), so a 4th
        # digit would be reporting noise.
        "vs_baseline": round(m["ratio"], 3),
    }
    if use_tpu:
        out["tokens_per_sec_raw"] = round(m["tokens_per_sec_raw"], 1)
        out["device_kind"] = m["device_kind"]
        out["n_params"] = m["n_params"]
        # Model FLOPs utilization: 6 * params * tokens/s over chip peak.
        out["mfu"] = round(
            6 * m["n_params"] * m["tokens_per_sec_framework"]
            / _peak_bf16_flops(m["device_kind"]),
            4,
        )
    print(json.dumps(out))


def _peak_bf16_flops(device_kind: str) -> float:
    """Per-chip peak bf16 FLOPs/s by device kind (public spec sheets)."""
    kind = device_kind.lower()
    for key, peak in (
        ("v5 lite", 197e12),
        ("v5e", 197e12),
        ("v5p", 459e12),
        ("v6", 918e12),
        ("v4", 275e12),
        ("v3", 123e12),
        ("v2", 46e12),
    ):
        if key in kind:
            return peak
    raise ValueError(f"no peak bf16 FLOP/s on record for device kind {device_kind!r}")


if __name__ == "__main__":
    main()
