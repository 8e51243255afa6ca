"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at the
full width of the one architecture the repo supports (models/transformer.py:
RoPE, GQA, SwiGLU, RMSNorm, sliding window), with random weights from a seed:

1. trainer: ``JaxTrainer`` -> ``TrainWorker`` actor -> ``make_train_step``,
   f32 parameters + AdamW, remat + fused loss, a few steps on one fixed
   seeded batch of 4096-token sequences. Loss must be finite and falling;
   the lowered step must contain the three Mosaic flash-attention kernels;
   and, outside any timing, fwd and bwd of ``flash_attention`` at the
   smoke's shapes must agree with ``_xla_attention``.
2. server: ``serve.run`` of ``LLMDeployment`` on one chip, requests over
   HTTP/SSE through the proxy. Tokens must be in vocabulary, the same prompt
   twice must give the same tokens, every stream must end in ``[DONE]``.

The driver process never imports jax: a chip belongs to one process at a
time, first the trainer's worker, then (once that process is gone) the
server's replica. A phase whose worker does not report ``platform == "tpu"``
fails the run; there is no CPU configuration, interpret-mode kernel or XLA
attention fallback for this script to pick on its own.

Widths are those of the public ``config.json`` of mistralai/Mistral-7B-v0.1
(hidden 4096, 32 heads / 8 KV heads, head_dim 128, d_ff 14336, vocab 32000,
rope_theta 10000, rms_norm_eps 1e-5, sliding_window 4096, untied head,
32 layers), as given in ISSUE 21 and the model-configs catalog; there is no
network here, so they were not re-checked against the hub. No width is cut.
DEPTH is cut to fit one 16 GB v5e chip: one layer is 218M parameters and
embedding + head 262M, so the trainer holds 2 of 32 layers (0.70B x 16 B
= 11.2 GB of parameters, gradients and Adam state) and the server 16 of 32
(3.75B x 2 B = 7.5 GB of bf16 weights beside its KV pool).

On a host with several chips the trainer's one worker takes all of them
(parameters and batch placed from ``session.get_mesh()``) and reports every
device's shards and memory; the server still takes one chip.

Last line of stdout on success: {"ok": true, "device": {...}} with the device
as jax reports it. Any failure exits non-zero and prints no such line.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MISTRAL_7B = dict(
    vocab_size=32000,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    rope_theta=10000.0,
    norm_eps=1e-5,
    sliding_window=4096,
    tie_embeddings=False,
    dtype="bfloat16",
)
TRAIN_LAYERS = 2  # of 32
SERVE_LAYERS = 16  # of 32
TRAIN_SEQ = 4096
TRAIN_STEPS = 4
# The flash kernels compute in bf16 with f32 accumulation; the reference is
# f32 at "highest" matmul precision. bf16 keeps 8 significant bits, and P is
# rounded to bf16 before the PV / dV matmuls, so element errors of a few
# 2^-9 of the largest value are expected: the v5e measured 0.28-0.55% of
# max|ref| (PR 21 probe). 2^-6 = 1.6% leaves 3x headroom and still fails an
# 8-bit-float or wrongly-masked kernel.
KERNEL_TOL = 2.0**-6
FLASH_KERNELS = ("_flash_kernel", "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel")
SERVE_ENGINE = dict(num_slots=4, block_size=16, max_model_len=2560, num_blocks=641)
SERVE_PROMPT_LENS = (512, 1024, 2048, 1536, 768)
SERVE_NEW_TOKENS = (32, 48, 64, 40, 56)


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# worker side of the trainer phase (runs inside the TrainWorker actor)
# ---------------------------------------------------------------------------


def _kernel_check(shape: dict, interpret: bool) -> dict:
    """fwd and bwd of flash_attention against _xla_attention at one shape,
    for each window. The reference runs a few heads at a time so its
    [h, T, T] f32 score tensors fit beside nothing else."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import _xla_attention, flash_attention

    T, H, KV, D = shape["seq"], shape["heads"], shape["kv_heads"], shape["head_dim"]
    group = min(4, H)
    scale = D**-0.5
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(kq, (1, T, H, D), jnp.bfloat16)
    do = jax.random.normal(kd, (1, T, H, D), jnp.bfloat16)
    # GQA as the model runs it: heads before tokens, k and v at KV heads, read in place by the kernels. The
    # reference repeats them to H heads, and a KV head's cotangent is the sum over its group's query heads.
    k = jax.random.normal(kk, (1, T, KV, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, T, KV, D), jnp.bfloat16)
    swap = lambda x: x.transpose(0, 2, 1, 3)
    repeated = lambda x: jnp.repeat(x, H // KV, axis=2)
    summed = lambda dx: dx.reshape(1, T, KV, H // KV, D).sum(axis=3)

    def flash(window):
        def run(q, k, v, do):
            out, vjp = jax.vjp(
                lambda a, b, c: flash_attention(
                    a, b, c, causal=True, window=window,
                    force_pallas=True, interpret=interpret,
                ),
                swap(q), swap(k), swap(v),
            )
            return tuple(swap(x) for x in (out, *vjp(swap(do))))

        return jax.jit(run)

    def reference(window):
        def one_group(args):
            qg, kg, vg, dog = (x.astype(jnp.float32) for x in args)
            with jax.default_matmul_precision("highest"):
                out, vjp = jax.vjp(
                    lambda a, b, c: _xla_attention(a, b, c, True, scale, window=window),
                    qg, kg, vg,
                )
                return (out, *vjp(dog))

        def run(q, k, v, do):
            # [1, T, H, D] -> [H/group, 1, T, group, D] and back.
            split = lambda x: jnp.moveaxis(x.reshape(1, T, H // group, group, D), 2, 0)
            outs = jax.lax.map(one_group, tuple(split(x) for x in (q, repeated(k), repeated(v), do)))
            out, dq, dk, dv = (jnp.moveaxis(o, 0, 2).reshape(1, T, H, D) for o in outs)
            return out, dq, summed(dk), summed(dv)

        return jax.jit(run)

    result = {}
    for window in shape["windows"]:
        got = flash(window)(q, k, v, do)
        want = reference(window)(q, k, v, do)
        errs = {}
        for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
            ref_max = float(jnp.max(jnp.abs(w)))
            if not err <= KERNEL_TOL * ref_max:  # also catches NaN
                raise SmokeFailure(
                    f"flash_attention {name} at window={window} T={T} D={D}: max error "
                    f"{err:.4g} exceeds {KERNEL_TOL:.4g} x max|ref| {ref_max:.4g}"
                )
            errs[name] = round(err / ref_max, 5)
        result[f"window={window}"] = errs
    return result


def train_loop(config: dict):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.air import session
    from ray_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        make_train_step,
        param_logical_axes,
    )
    from ray_tpu.parallel.mesh import logical_to_spec, shard_by_logical_axes
    from ray_tpu.util.device_report import device_report

    device = device_report()
    if device["platform"] != config["platform"]:
        raise SmokeFailure(
            f"trainer worker runs on platform {device['platform']!r} "
            f"({device['device_kind']}), need {config['platform']!r}"
        )
    on_tpu = device["platform"] == "tpu"
    report = {"compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}

    # Outside any timing, and before the parameters take the memory.
    check = dict(config["kernel_check"])
    report["kernel_rel_err"] = _kernel_check(check, check.pop("interpret"))

    model = dict(config["model"])
    model["dtype"] = jnp.dtype(model["dtype"]).type
    cfg = TransformerConfig(
        **model, max_seq_len=config["seq"], param_dtype=jnp.float32,
        remat=True, fused_loss=True,
    )
    mesh = session.get_mesh()
    params = shard_by_logical_axes(
        init_params(jax.random.PRNGKey(0), cfg), param_logical_axes(cfg), mesh
    )
    # 1e-4: at 1e-3 the first Adam step memorises the one batch and the next
    # ones overshoot (v5e, PR 21: 10.87, 0.09, 1.30, 5.16).
    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)
    batch_size = config["batch_per_chip"] * device["count"]
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, config["seq"] + 1), 0, cfg.vocab_size
    )
    batch = {
        "tokens": jax.device_put(
            tokens, NamedSharding(mesh, logical_to_spec(("batch", None)))
        )
    }

    lowered = jax.jit(
        make_train_step(cfg, opt, mesh=mesh), donate_argnums=(0, 1)
    ).lower(params, opt_state, batch)
    if on_tpu:
        # No quiet _xla_attention: the step that will run holds all three
        # Mosaic kernels.
        text = lowered.as_text()
        missing = [k for k in FLASH_KERNELS if f'kernel_name = "{k}"' not in text]
        if missing:
            raise SmokeFailure(f"lowered train step lacks Mosaic kernels {missing}")
    t0 = time.perf_counter()
    step = lowered.compile()
    report["compile_s"] = round(time.perf_counter() - t0, 2)
    mem = step.memory_analysis()
    report["step_bytes"] = {
        "arguments": mem.argument_size_in_bytes,
        "outputs": mem.output_size_in_bytes,
        "aliased": mem.alias_size_in_bytes,
        "temporaries": mem.temp_size_in_bytes,
    }

    losses = []
    t0 = time.perf_counter()
    for _ in range(config["steps"]):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    report["steps_s"] = round(time.perf_counter() - t0, 2)
    report["losses"] = [round(x, 4) for x in losses]
    report["n_params"] = sum(int(x.size) for x in jax.tree.leaves(params))
    report["device"] = device_report()  # again: peak bytes after the steps
    leaf = params["layers"]["wi"]
    report["shards"] = [
        {
            "device": s.device.id,
            "wi_shard": list(s.data.shape),
            "tokens_shard": list(t.data.shape),
        }
        for s, t in zip(leaf.addressable_shards, batch["tokens"].addressable_shards)
    ]
    session.report(report)


# ---------------------------------------------------------------------------
# driver side (never imports jax)
# ---------------------------------------------------------------------------


def train_phase(
    model: dict,
    *,
    n_chips: int,
    seq: int,
    steps: int,
    kernel_check: dict,
    platform: str = "tpu",
) -> dict:
    """JaxTrainer with one worker holding ``n_chips``; returns the worker's
    report after checking it. ``platform`` is what the worker must run on."""
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    trainer = JaxTrainer(
        train_loop,
        train_loop_config=dict(
            model=model, seq=seq, steps=steps, batch_per_chip=1,
            kernel_check=kernel_check, platform=platform,
        ),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True, tpu_per_worker=n_chips),
    )
    r = trainer.fit().metrics
    losses = r["losses"]
    _check(len(losses) == steps, f"trainer reported {len(losses)} of {steps} steps")
    _check(all(x == x and abs(x) != float("inf") for x in losses), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    dev = r["device"]
    _check(dev["platform"] == platform, f"trainer platform {dev['platform']!r}")
    if platform == "tpu":  # a CPU stand-in sees the host's devices, not the grant
        _check(dev["count"] == n_chips, f"trainer saw {dev['count']} of {n_chips} chips")
        _check(len(r["shards"]) == n_chips, f"shards on {len(r['shards'])} of {n_chips} chips")
        for mem in dev["memory"]:
            _check(mem["bytes_in_use"] > 0, f"device {mem['id']} holds no memory")
    return r


def wait_for_exit(pid: int, timeout_s: float = 60.0) -> float:
    """The trainer's worker held the chip; the server's replica can open it
    only once that process is gone. ``remove_actor`` kills asynchronously, so
    look. Returns the seconds waited."""
    t0 = time.monotonic()
    while os.path.exists(f"/proc/{pid}"):
        _check(time.monotonic() - t0 < timeout_s, f"trainer worker {pid} still alive after {timeout_s}s")
        time.sleep(0.05)
    return round(time.monotonic() - t0, 2)


def _sse_request(url: str, tokens: list, max_new_tokens: int) -> dict:
    """One greedy streaming request; returns its tokens and wall time."""
    req = urllib.request.Request(
        url, data=json.dumps({"tokens": tokens, "max_new_tokens": max_new_tokens}).encode()
    )
    t0 = time.monotonic()
    out, done, buf = [], False, b""
    with urllib.request.urlopen(req, timeout=900) as resp:
        while not done:
            chunk = resp.read(256)
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if event == b"data: [DONE]":
                    done = True
                elif event.startswith(b"data: "):
                    out.append(json.loads(event[6:])["token"])
    return {"tokens": out, "done": done, "wall_s": round(time.monotonic() - t0, 2)}


def serve_phase(
    model: dict,
    engine: dict,
    *,
    prompt_lens: tuple,
    new_tokens: tuple,
    platform: str = "tpu",
) -> dict:
    """serve.run of LLMDeployment on one chip, requests over HTTP/SSE through
    the proxy; returns timings and the replica's device report."""
    import random

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    rng = random.Random(21)
    prompts = [[rng.randrange(model["vocab_size"]) for _ in range(n)] for n in prompt_lens]
    serve.start()
    try:
        app = serve.deployment(ray_actor_options={"num_tpus": 1})(LLMDeployment).bind(
            dict(model, param_dtype=model["dtype"], max_seq_len=engine["max_model_len"]),
            engine_config=engine,
        )
        t0 = time.monotonic()
        handle = serve.run(app, route_prefix="/llm")
        ready_s = round(time.monotonic() - t0, 2)
        host, port = serve.http_address()
        url = f"http://{host}:{port}/llm"

        # First request alone: the prefill and decode programs compile inside
        # it. Then the same prompt again, which must give the same tokens.
        first = _sse_request(url, prompts[0], new_tokens[0])
        again = _sse_request(url, prompts[0], new_tokens[0])
        results = [first, again] + [None] * (len(prompts) - 1)

        def worker(i):
            results[i + 1] = _sse_request(url, prompts[i], new_tokens[i])

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(1, len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        wants = [new_tokens[0]] + list(new_tokens)
        for i, (res, want) in enumerate(zip(results, wants)):
            _check(res is not None, f"request {i} did not return")
            _check(res["done"], f"request {i} stream did not end in [DONE]")
            _check(len(res["tokens"]) == want, f"request {i}: {len(res['tokens'])} of {want} tokens")
            _check(
                all(0 <= t < model["vocab_size"] for t in res["tokens"]),
                f"request {i}: token outside the vocabulary",
            )
        _check(first["tokens"] == again["tokens"], "the same prompt twice gave different tokens")
        stats = ray_tpu.get(handle.get_stats.remote(), timeout=60)
        sdev = stats["device"]
        _check(sdev["platform"] == platform, f"replica platform {sdev['platform']!r}")
        if platform == "tpu":
            _check(sdev["count"] == 1, f"replica saw {sdev['count']} chips, granted 1")
        return {
            "ready_s": ready_s,
            "first_request_s": first["wall_s"],
            "repeat_request_s": again["wall_s"],
            # Both programs compile inside the first request; the repeat also
            # skips the prompt blocks the prefix cache kept, so this slightly
            # overstates compilation.
            "compile_s": round(first["wall_s"] - again["wall_s"], 2),
            "request_wall_s": [r["wall_s"] for r in results],
            "device": sdev,
            "finished": stats["finished"],
        }
    finally:
        serve.shutdown()


def preflight() -> int:
    """Chips on this host, found without importing jax; exits non-zero,
    naming the platform a worker would get, when there is no chip to run on."""
    from ray_tpu._private.node import detect_tpu_chips, pinned_jax_platform

    pinned = pinned_jax_platform()
    if pinned not in ("", "tpu"):
        sys.exit(f"chip_smoke: JAX_PLATFORMS puts workers on platform {pinned!r}; need 'tpu'")
    n_chips = detect_tpu_chips()
    if n_chips == 0:
        sys.exit(
            "chip_smoke: no TPU chip on this host (no /dev/accel<n> or /dev/vfio/<n>); "
            "jax would run on platform 'cpu'; need 'tpu'"
        )
    return n_chips


def _peak(device: dict) -> list:
    return [m["peak_bytes_in_use"] for m in device["memory"]]


def main():
    from ray_tpu.util.compile_cache import export_compile_cache_dir

    cache_dir = export_compile_cache_dir(__file__)
    n_chips = preflight()
    import ray_tpu

    ray_tpu.init(num_tpus=n_chips)
    try:
        raylet = ray_tpu._global_node.raylet
        print(
            f"[chip_smoke] chips={n_chips} compile_cache={cache_dir} "
            f"arena={type(raylet.arena).__name__} sched_core={type(raylet._sched).__name__}",
            flush=True,
        )
        train = train_phase(
            dict(MISTRAL_7B, n_layers=TRAIN_LAYERS),
            n_chips=n_chips,
            seq=TRAIN_SEQ,
            steps=TRAIN_STEPS,
            kernel_check=dict(
                seq=TRAIN_SEQ,
                heads=MISTRAL_7B["n_heads"],
                kv_heads=MISTRAL_7B["n_kv_heads"],
                head_dim=MISTRAL_7B["d_model"] // MISTRAL_7B["n_heads"],
                windows=(0, MISTRAL_7B["sliding_window"]),
                interpret=False,
            ),
        )
        _check(train["compile_cache_dir"] == cache_dir, f"worker saw cache dir {train['compile_cache_dir']!r}")
        dev = train["device"]
        print(
            f"[chip_smoke] trainer: platform={dev['platform']} kind={dev['device_kind']!r} "
            f"count={dev['count']} layers={TRAIN_LAYERS}/32 params={train['n_params']} "
            f"compile_s={train['compile_s']} steps_s={train['steps_s']} losses={train['losses']} "
            f"peak_bytes={_peak(dev)} step_bytes={train['step_bytes']} "
            f"kernel_rel_err={train['kernel_rel_err']}",
            flush=True,
        )
        print(f"[chip_smoke] trainer shards: {train['shards']} memory={dev['memory']}", flush=True)
        waited = wait_for_exit(dev["pid"])
        print(f"[chip_smoke] trainer worker {dev['pid']} gone after {waited}s", flush=True)
        served = serve_phase(
            dict(MISTRAL_7B, n_layers=SERVE_LAYERS),
            SERVE_ENGINE,
            prompt_lens=SERVE_PROMPT_LENS,
            new_tokens=SERVE_NEW_TOKENS,
        )
        sdev = served["device"]
        print(
            f"[chip_smoke] server: platform={sdev['platform']} kind={sdev['device_kind']!r} "
            f"count={sdev['count']} layers={SERVE_LAYERS}/32 ready_s={served['ready_s']} "
            f"compile_s={served['compile_s']} (first {served['first_request_s']} - repeat "
            f"{served['repeat_request_s']}) request_wall_s={served['request_wall_s']} "
            f"finished={served['finished']} peak_bytes={_peak(sdev)}",
            flush=True,
        )
    finally:
        ray_tpu.shutdown()
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev["platform"],
                    "kind": dev["device_kind"],
                    "count": dev["count"],
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
