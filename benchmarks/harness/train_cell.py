"""One run of a training cell: ``JaxTrainer`` -> ``TrainWorker`` ->
``make_train_step`` with one worker that holds all the cell's chips.

``train_loop`` is the benchmark's own loop and runs inside the worker, the only
process that touches jax: device check; parameters from the seed; the check
against the reference before the optimizer state takes its memory; compile;
two warm steps; then steps for ``seconds``, each on a fresh seeded batch made
on the host and placed with ``prepare_batch``, the loss fetched with a lag and
reported through ``session.report`` every few steps, as a real loop does. A
traced run puts a profiler slice in the middle of the window; starting and
stopping the profiler (it writes the trace file) is the benchmark's own work
and is taken out of the window.
"""

from __future__ import annotations

import os
import time

from benchmarks.harness import registry
from benchmarks.harness.common import CellFailure, log


def _rel_l2(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.linalg.norm((a - b).ravel()) / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30)


def _reference_check(cell, cfg, mesh, params, batch):
    """Step-0 loss and gradients of the system against the reference's, on the
    first batch. The system's gradient is the parameter delta of one
    ``make_train_step`` step under plain SGD, the path users run, over the
    learning rate. The rate is a large power of two so that the delta, not the
    parameter it is subtracted from, sets the float32 rounding: at 1.0 the
    embedding's delta (entries ~1e-5 beside parameters ~1) lost two digits."""
    import jax
    import optax

    from ray_tpu.models.transformer import make_train_step

    reference, cfg_file = registry.load_architecture(cell, "reference"), cell["config"]

    t0 = time.monotonic()
    rate = 2.0**12
    sgd = optax.sgd(rate)
    step = jax.jit(make_train_step(cfg, sgd, mesh=mesh))
    new_params, _, sys_loss = step(params, sgd.init(params), batch)
    sys_grads = jax.jit(lambda p, q: jax.tree.map(lambda a, b: (a - b) / rate, p, q))(params, new_params)
    sys_loss = float(sys_loss)
    del new_params
    t1 = time.monotonic()
    ref_loss, ref_grads = jax.jit(
        jax.value_and_grad(lambda p, t: reference.loss(p, t, cfg_file))
    )(params, batch["tokens"])
    ref_loss = float(ref_loss)
    t2 = time.monotonic()
    errs = jax.jit(lambda g, r: jax.tree.map(_rel_l2, g, r))(sys_grads, ref_grads)
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): float(v)
        for path, v in jax.tree_util.tree_leaves_with_path(errs)
    }
    return {
        "loss": sys_loss,
        "ref_loss": ref_loss,
        "grad_rel_l2": flat,
        "system_s": t1 - t0,
        "reference_s": t2 - t1,
    }


def train_loop(config: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.harness import trace
    from benchmarks.harness.device import device_line
    from ray_tpu.air import session
    from ray_tpu.models.transformer import (
        TransformerConfig, init_params, make_train_step, param_logical_axes,
    )
    from ray_tpu.parallel.mesh import shard_by_logical_axes
    from ray_tpu.train.jax.train_loop_utils import prepare_batch

    cell, seed = config["cell"], int(config["seed"])
    cfg_file, job = cell["config"], cell["traffic"]
    traced, seconds = bool(config["traced"]), float(config["seconds"])
    device = device_line()
    if device["platform"] != config["platform"]:
        raise CellFailure(f"worker runs on platform {device['platform']!r}, need {config['platform']!r}")
    if config["platform"] == "tpu" and device["count"] != cell["chips"]:
        raise CellFailure(f"worker sees {device['count']} chips, the cell asks for {cell['chips']}")
    clock = {"t_loop": time.monotonic()}
    dep = cfg_file["deployment"]
    T = int(job["seq_len"])
    model = registry.load_architecture(cell, "config").model_config(cfg_file, T, dep["param_dtype"])
    for key in ("dtype", "param_dtype"):
        model[key] = jnp.dtype(model[key]).type
    cfg = TransformerConfig(**model, remat=bool(dep["remat"]), fused_loss=bool(dep["fused_loss"]))
    mesh = session.get_mesh()
    params = shard_by_logical_axes(
        init_params(jax.random.PRNGKey(seed), cfg), param_logical_axes(cfg), mesh
    )
    B = int(job["batch_per_chip"]) * len(jax.devices())
    data = np.random.default_rng(seed)

    def next_batch():
        tokens = data.integers(0, cfg.vocab_size, (B, T + 1), dtype=np.int32)
        return prepare_batch({"tokens": tokens}, mesh)

    batch = next_batch()
    jax.block_until_ready(params)
    clock["params_s"] = time.monotonic() - clock["t_loop"]
    check = _reference_check(cell, cfg, mesh, params, batch)
    clock["check_s"] = time.monotonic() - clock["t_loop"] - clock["params_s"]

    opt = optax.adamw(float(dep["learning_rate"]))
    opt_state = opt.init(params)
    lowered = jax.jit(make_train_step(cfg, opt, mesh=mesh), donate_argnums=(0, 1)).lower(
        params, opt_state, batch
    )
    kernels_in_step = [
        k for k in dep["mosaic_kernels"] if f'kernel_name = "{k}' in lowered.as_text()
    ] if config["platform"] == "tpu" else []
    step = lowered.compile()
    clock["compile_s"] = time.monotonic() - clock["t_loop"] - clock["params_s"] - clock["check_s"]
    mem = step.memory_analysis()
    program_bytes = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    ) if mem is not None else 0
    for _ in range(2):  # warm: first execution, allocator, transfer path
        params, opt_state, loss = step(params, opt_state, next_batch())
    first_loss = float(loss)
    clock["first_step_s"] = time.monotonic() - clock["t_loop"]

    # -- the window -----------------------------------------------------
    every = int(job["report_every"])
    slice_s = float(job.get("trace_slice_s", 3.0))
    trace_dir = os.path.join(config["scratch"], "trace")
    t_open = time.monotonic()
    session.report({"event": "open", "t_open": t_open})
    pending, losses, steps, tracing, slice_len = [], [], 0, 0, None
    paused = 0.0  # seconds the profiler took to start and to stop: not the system's
    stall = (0.0, 0)  # longest time between two loss fetches and the step it ended at
    t_fetch = t_open
    while time.monotonic() - t_open - paused < seconds:
        if traced and tracing == 0 and time.monotonic() - t_open >= (seconds - slice_s) / 2:
            jax.block_until_ready(loss)  # the steps in flight are the window's, not the pause's
            t_pause = time.monotonic()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing, t_trace = 1, time.monotonic()
            paused += t_trace - t_pause
        params, opt_state, loss = step(params, opt_state, next_batch())
        steps += 1
        pending.append(loss)
        if len(pending) > every:
            # The loss of `every` steps ago: the host stays that far ahead.
            losses.append(float(pending.pop(0)))
            now = time.monotonic()
            stall, t_fetch = max(stall, (now - t_fetch, steps)), now
            if steps % every == 0:
                session.report({"event": "progress", "step": steps, "loss": losses[-1]})
        if tracing == 1 and time.monotonic() - t_trace >= slice_s:
            jax.block_until_ready(loss)
            t_pause = time.monotonic()
            slice_len = t_pause - t_trace
            jax.profiler.stop_trace()
            tracing = 2
            paused += time.monotonic() - t_pause
    losses += [float(x) for x in pending]  # ends in a host fetch: all steps are done
    window_s = time.monotonic() - t_open - paused
    if tracing == 1:
        slice_len = time.monotonic() - t_trace
        jax.profiler.stop_trace()

    reduced = None
    if traced:
        reduced = trace.reduce_file(trace_dir, cfg_file["trace_programs"])
        reduced["slice_s"] = slice_len
    session.report(
        {
            "event": "done",
            "t_open": t_open,
            "window_s": window_s,
            "steps": steps,
            "tokens_per_step": B * T,
            "longest_fetch_gap": stall,
            "batch": B,
            "losses": losses,
            "first_loss": first_loss,
            "check": check,
            "clock": {k: v for k, v in clock.items() if k != "t_loop"},
            "kernels_in_step": kernels_in_step,
            "trace": reduced,
            "device": device_line(program_bytes),
            "pid": os.getpid(),
        }
    )


def run(cell: dict, *, seed: int, seconds: float, traced: bool, t_process: float,
        scratch: str, platform: str = "tpu") -> dict:
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    cfg = cell["config"]
    trainer = JaxTrainer(
        train_loop,
        train_loop_config=dict(
            cell=cell, seed=seed, seconds=seconds, traced=traced, platform=platform, scratch=scratch,
        ),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True, tpu_per_worker=cell["chips"]),
        # The run directory (checkpoint manager) under this run's scratch, which
        # run.py removes: the default is a fixed /tmp/ray_tpu_results.
        run_config=RunConfig(storage_path=os.path.join(scratch, "results")),
    )
    t0 = time.monotonic()
    r = trainer.fit().metrics
    if r.get("event") != "done":
        raise CellFailure(f"the trainer's last report is {r.get('event')!r}, not 'done'")
    clock = dict(r["clock"])
    # fit() start to the end of the warm steps, on this process's clock:
    # the worker's monotonic clock is the same one (same host).
    clock["trainer_first_step_s"] = r["t_open"] - t0
    clock["setup_s"] = r["t_open"] - t_process
    clock["worker_start_s"] = clock["trainer_first_step_s"] - clock["first_step_s"]

    tol = cfg["check"]
    chk = r["check"]
    correct = True
    finite = all(x == x and abs(x) != float("inf") for x in r["losses"])
    if not finite or not r["losses"]:
        correct = False
        log(f"check: a loss of the window is not finite: {r['losses'][:8]}")
    if not abs(chk["loss"] - chk["ref_loss"]) <= tol["loss_abs_tol"]:
        correct = False
        log(f"check: step-0 loss {chk['loss']} against the reference's {chk['ref_loss']}")
    worst = max(chk["grad_rel_l2"].values())
    if not worst <= tol["grad_rel_l2_tol"]:
        correct = False
        log(f"check: gradient relative L2 {chk['grad_rel_l2']} over {tol['grad_rel_l2_tol']}")
    if platform == "tpu" and r["kernels_in_step"] != cfg["deployment"]["mosaic_kernels"]:
        correct = False
        log(f"check: the lowered step holds only the kernels {r['kernels_in_step']}")
    gap_s, at_step = r["longest_fetch_gap"]
    log(f"train: steps={r['steps']} window={r['window_s']:.3f}s; longest time between two loss "
        f"fetches {gap_s:.3f}s (step {at_step}); check={chk} clock={clock}")
    return {
        "attempted": r["steps"],
        "failed": sum(not (x == x and abs(x) != float("inf")) for x in r["losses"]),
        "train": r, "clock": clock, "trace": r["trace"], "device": r["device"],
        "correct": correct, "notes": {"check": chk, "losses": r["losses"][-4:]},
        "worker_pid": r["pid"],
    }
