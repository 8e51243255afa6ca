"""One run of a serving cell: ``serve.run(LLMDeployment)`` -> proxy -> engine on
one chip, load over HTTP/SSE from this process, which never imports jax.

Order of a run: replica up; the check prompts, each over HTTP twice (which
also warms both compiled programs); in a traced run the probes of Serve's
overhead; then the schedule, with a pre-roll so that the window opens on a
loaded system; in a traced run a profiler slice in the middle of the window
and a poll of the engine's counters once a second. After the window the
device's peak is read, and only then is the float32 reference run over the
check prompts' tokens: its layer sits beside the model on the chip, and the
peak a run reports is the deployment's, not the yardstick's.
"""

from __future__ import annotations

import os
import random
import threading
import time

from benchmarks.harness import client, traffic
from benchmarks.harness.common import CellFailure, log, model_config


def _one_request(url, tokens, max_new_tokens, vocab):
    """A single greedy request outside any load; returns (tokens, ttft_ms)."""
    s = client.fetch(url, dict(tokens=tokens, max_new_tokens=max_new_tokens,
                               temperature=0.0, top_k=0, seed=0))
    if s.error or not s.done or len(s.tokens) != max_new_tokens:
        raise CellFailure(f"check request failed: error={s.error} done={s.done} n={len(s.tokens)}")
    if any(not 0 <= t < vocab for t in s.tokens):
        raise CellFailure("check request returned a token outside the vocabulary")
    return s.tokens, (s.times[0] - s.sent) * 1000.0


def run(cell: dict, *, seed: int, seconds: float, traced: bool, t_process: float,
        scratch: str, platform: str = "tpu") -> dict:
    """Returns what ``run.py`` builds the line from: ``client`` (summary),
    ``clock``, ``counters``, ``trace`` (reduced or None), ``device``, ``correct``."""
    import ray_tpu
    from ray_tpu import serve

    from benchmarks.harness.deployment import BenchLLMDeployment

    cfg, mix = cell["config"], cell["traffic"]
    dep = cfg["deployment"]
    engine = dict(dep["engine"])
    vocab = cfg["vocab_size"]
    clock, notes = {}, {}
    serve.start()
    try:
        app = serve.deployment(ray_actor_options={"num_tpus": 1})(BenchLLMDeployment).bind(
            model_config(cfg, engine["max_model_len"], dep["param_dtype"]),
            engine_config=engine,
            init_seed=int(seed),
        )
        t0 = time.monotonic()
        handle = serve.run(app, route_prefix="/llm")
        clock["replica_ready_s"] = time.monotonic() - t0
        host, port = serve.http_address()
        url = f"http://{host}:{port}/llm"
        device = ray_tpu.get(handle.device.remote(), timeout=120)
        if device["platform"] != platform:
            raise CellFailure(f"replica runs on platform {device['platform']!r}, need {platform!r}")
        if platform == "tpu" and device["count"] != cell["chips"]:
            raise CellFailure(f"replica sees {device['count']} chips, the cell asks for {cell['chips']}")
        log(f"replica ready in {clock['replica_ready_s']:.2f}s on {device}")

        # -- correctness, which is also the warm-up of both programs --------
        check = cfg["check"]
        rng = random.Random(int(seed) ^ 0x5EED)
        correct, sequences, n_prompt = True, [], []
        t0 = time.monotonic()
        for n in check["prompt_lens"]:
            prompt = [rng.randrange(vocab) for _ in range(n)]
            first, _ = _one_request(url, prompt, check["new_tokens"], vocab)
            again, _ = _one_request(url, prompt, check["new_tokens"], vocab)
            if first != again:
                correct = False
                log(f"check: the same {n}-token prompt twice gave different tokens")
            sequences.append(prompt + first)
            n_prompt.append(n)
        clock["check_s"] = time.monotonic() - t0

        # -- Serve's share of a first token, on the idle engine --------------
        if traced:
            over = []
            for _ in range(int(check.get("probe_pairs", 5))):
                a = [rng.randrange(vocab) for _ in range(check["probe_len"])]
                b = [rng.randrange(vocab) for _ in range(check["probe_len"])]
                _, http_ms = _one_request(url, a, 2, vocab)
                engine_ms = ray_tpu.get(handle.engine_probe.remote(b, 2), timeout=120)
                over.append(http_ms - engine_ms)
            clock["serve_path_overhead_ms"] = client.median(over)

        # -- the window ----------------------------------------------------
        plan = traffic.schedule(mix, seed, seconds, vocab)
        preroll = float(mix.get("preroll_s", 0.0))
        polls, trace_info, stop = [], {}, threading.Event()
        trace_dir = os.path.join(scratch, "trace")

        def side(t_open):
            """Traced runs only: counters once a second, and the slice."""
            slice_s = float(mix.get("trace_slice_s", 3.0))
            start_at = t_open + max(0.0, (seconds - slice_s) / 2)
            started = stopped = False
            while not stop.is_set():
                now = time.monotonic()
                if not started and now >= start_at:
                    ray_tpu.get(handle.trace_start.remote(trace_dir), timeout=120)
                    started = True
                elif started and not stopped and now >= start_at + slice_s:
                    trace_info["slice_s"] = ray_tpu.get(handle.trace_stop.remote(), timeout=300)
                    stopped = True
                elif t_open <= now < t_open + seconds:
                    polls.append(ray_tpu.get(handle.get_stats.remote(), timeout=60)["running"])
                stop.wait(1.0)
            if started and not stopped:
                trace_info["slice_s"] = ray_tpu.get(handle.trace_stop.remote(), timeout=300)

        thread = None

        def on_open(t_open):
            nonlocal thread
            clock["setup_s"] = t_open - t_process
            if traced:
                thread = threading.Thread(target=side, args=(t_open,), name="bench-side")
                thread.start()

        samples, t_open = client.run_load(
            url, plan, preroll_s=preroll, seconds=seconds,
            grace_s=float(mix.get("grace_s", 5.0)), on_open=on_open,
        )
        stop.set()
        if thread is not None:
            thread.join(timeout=600)
            if thread.is_alive():
                raise CellFailure("the trace thread did not end")
        summary = client.summarise(samples, t_open, seconds, vocab)
        log(f"generator lateness ms: {summary['generator_late_ms']}; errors: {summary['errors']}")
        ttft = summary["ttft_ms"]
        if ttft and "open" in plan:
            # By thirds of the window, in order of arrival: a backlog that grows shows here.
            k = max(1, len(ttft) // 3)
            thirds = [round(client.median(ttft[i:i + k]), 1) for i in (0, k, len(ttft) - k)]
            log(f"ttft ms: p50={client.median(ttft):.1f} p90={client.percentile(ttft, 90):.1f} "
                f"max={max(ttft):.1f}; median by thirds of the window {thirds}")

        counters = ray_tpu.get(handle.get_stats.remote(), timeout=60)
        counters["running_polls"] = polls
        log("engine counters: " + str({k: v for k, v in counters.items() if isinstance(v, int)}))
        reduced = None
        if traced:
            reduced = ray_tpu.get(handle.trace_reduce.remote(cfg["trace_programs"]), timeout=900)
            reduced["slice_s"] = trace_info.get("slice_s")
        device = ray_tpu.get(handle.device.remote(), timeout=120)

        # -- the reference, after the peak is read ----------------------------
        # The client cut the streams still open; the engine frees their slots
        # on its next iterations. The reference waits for that: a step in
        # flight leaves 1.3 of 16.9 GB free (PERF.md section 4), too little
        # for its float32 layer and logits.
        t0 = time.monotonic()
        while True:
            stats = ray_tpu.get(handle.get_stats.remote(), timeout=60)
            if stats["running"] == 0 and stats["waiting"] == 0:
                break
            if time.monotonic() - t0 > 60.0:
                raise CellFailure(f"engine still busy 60 s after the streams were cut: {stats['running']} running")
            time.sleep(0.25)
        gaps = ray_tpu.get(handle.reference_check.remote(cfg, sequences, n_prompt), timeout=600)
        notes["reference_gaps"] = gaps
        for n, g in zip(n_prompt, gaps):
            if not g["finite"] or not g["max_gap"] <= check["logit_gap_tol"]:
                correct = False
                log(f"check: prompt of {n}: reference logit gap {g} over {check['logit_gap_tol']}")
        clock["reference_s"] = time.monotonic() - t0
        log(f"reference done in {clock['reference_s']:.2f}s correct={correct} gaps={gaps}")
        return {
            "attempted": summary["attempted"], "failed": summary["failed"],
            "client": summary, "clock": clock, "counters": counters, "trace": reduced,
            "device": device, "correct": correct, "notes": notes,
        }
    finally:
        serve.shutdown()
