"""Load generator and SSE client: one asyncio loop in the driver process, which
never imports jax, and the arithmetic that turns what the client saw into the
end-to-end numbers. All times are ``time.monotonic()`` of this process.

Open loop: every request is sent when it is due, whatever the server does, and
its latency counts from the time it was *due*; how late the generator itself
ran is reported beside the results. Closed loop: each client sends its next
request when the last one ended.
"""

from __future__ import annotations

import asyncio
import json
import math
import time


def percentile(values: list, q: float) -> float:
    """q-th percentile (0-100) by linear interpolation between closest ranks,
    as numpy's default; ``inf`` entries (requests that never answered) sort last."""
    if not values:
        return float("nan")
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or math.isinf(xs[hi]):
        return float(xs[hi] if k > lo else xs[lo])
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: list) -> float:
    return percentile(values, 50.0)


class _Sample:
    __slots__ = ("due", "sent", "times", "tokens", "want", "done", "error")

    def __init__(self, due, want):
        self.due = due  # when it should have been sent (None: closed loop)
        self.sent = None
        self.times = []  # arrival time of each token event
        self.tokens = []
        self.want = want
        self.done = False  # saw [DONE]
        self.error = None


async def _stream(session, url: str, body: dict, sample: _Sample):
    sample.sent = time.monotonic()
    try:
        async with session.post(url, data=json.dumps(body)) as resp:
            if resp.status != 200:
                sample.error = f"HTTP {resp.status}"
                return
            buf = b""
            async for chunk in resp.content.iter_any():
                now = time.monotonic()
                buf += chunk
                while b"\n\n" in buf:
                    event, buf = buf.split(b"\n\n", 1)
                    if event == b"data: [DONE]":
                        sample.done = True
                    elif event.startswith(b"data: "):
                        sample.tokens.append(json.loads(event[6:])["token"])
                        sample.times.append(now)
    except asyncio.CancelledError:
        raise
    except Exception as e:  # a failed request is a result, not a crash
        sample.error = f"{type(e).__name__}: {e}"


def _body(req: dict, tokens: list, max_new_tokens: int) -> dict:
    return {
        "tokens": tokens,
        "max_new_tokens": max_new_tokens,
        "temperature": req["temperature"],
        "top_k": req["top_k"],
        "seed": req["seed"],
    }


async def _open_request(session, url, req, t_open, samples):
    due = t_open + req["due_s"]
    await asyncio.sleep(max(0.0, due - time.monotonic()))
    tokens, want = list(req["tokens"]), req["max_new_tokens"]
    sample = _Sample(due, want)
    samples.append(sample)
    await _stream(session, url, _body(req, tokens, want), sample)
    for follow in req.get("followups", ()):
        if sample.error or not sample.done:
            return
        due = time.monotonic() + follow["think_time_s"]
        await asyncio.sleep(follow["think_time_s"])
        tokens = tokens + sample.tokens + follow["new_tokens"]
        sample = _Sample(due, follow["max_new_tokens"])
        samples.append(sample)
        await _stream(session, url, _body(req, tokens, sample.want), sample)


async def _closed_client(session, url, reqs, samples):
    i = 0
    while True:
        req = reqs[i % len(reqs)]
        i += 1
        sample = _Sample(None, req["max_new_tokens"])
        samples.append(sample)
        await _stream(session, url, _body(req, req["tokens"], sample.want), sample)
        if sample.error:
            await asyncio.sleep(0.05)  # do not spin on a dead server


async def _drive(url, plan, preroll_s, seconds, grace_s, on_open):
    import aiohttp

    samples: list[_Sample] = []
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=connector) as session:
        t_open = time.monotonic() + preroll_s
        if "open" in plan:
            tasks = [
                asyncio.ensure_future(_open_request(session, url, r, t_open, samples))
                for r in plan["open"]
            ]
        else:
            tasks = [
                asyncio.ensure_future(_closed_client(session, url, reqs, samples))
                for reqs in plan["closed"]
            ]
        await asyncio.sleep(max(0.0, t_open - time.monotonic()))
        if on_open is not None:
            on_open(t_open)
        t_close = t_open + seconds
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
        # A request of the window that still waits for its first token gets
        # the grace; then every stream still open is cut by closing it.
        while time.monotonic() < t_close + grace_s and any(
            s.sent is not None and s.sent < t_close and not s.times and not s.error and not s.done
            for s in samples
        ):
            await asyncio.sleep(0.05)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return samples, t_open


async def _fetch(url, body):
    import aiohttp

    sample = _Sample(None, body["max_new_tokens"])
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        await _stream(session, url, body, sample)
    return sample


def fetch(url: str, body: dict):
    """One request alone, to its end; returns its sample."""
    return asyncio.run(_fetch(url, body))


def run_load(url, plan, *, preroll_s, seconds, grace_s, on_open=None):
    """Runs the plan against ``url``; returns (samples, t_open)."""
    return asyncio.run(_drive(url, plan, preroll_s, seconds, grace_s, on_open))


def summarise(samples, t_open: float, seconds: float, vocab_size: int) -> dict:
    """What the client saw, reduced. Requests of the window are, in an open
    loop, those due inside [t_open, t_open + seconds): one that has no first
    token when the grace is over has failed. In a closed loop they are those
    that streamed inside the window; one that still waits in the server's
    queue at the end (there are more clients than slots) has not been tried."""
    t_close = t_open + seconds
    window = [
        s for s in samples
        if s.sent is not None and (
            t_open <= s.due < t_close if s.due is not None
            else s.sent < t_close and (s.error is not None or any(t_open <= t < t_close for t in s.times))
        )
    ]
    failed = 0
    ttft = []
    for s in window:
        bad = (
            s.error is not None
            or not s.times
            or any(not (isinstance(t, int) and 0 <= t < vocab_size) for t in s.tokens)
            or len(s.tokens) > s.want
            or (s.done and len(s.tokens) != s.want)
        )
        failed += bad
        start = s.due if s.due is not None else s.sent
        ttft.append(float("inf") if bad else (s.times[0] - start) * 1000.0)
    gaps, tokens_in_window = [], 0
    for s in samples:
        tokens_in_window += sum(t_open <= t < t_close for t in s.times)
        gaps += [
            (b - a) * 1000.0 for a, b in zip(s.times, s.times[1:]) if t_open <= b < t_close
        ]
    late = [(s.sent - s.due) * 1000.0 for s in samples if s.due is not None and s.sent is not None]
    return {
        "attempted": len(window),
        "failed": int(failed),
        "finished": sum(s.done for s in window),
        "ttft_ms": ttft,
        "itl_ms": gaps,
        "tokens_in_window": tokens_in_window,
        "errors": sorted({s.error for s in samples if s.error})[:5],
        "generator_late_ms": {
            "p50": percentile(late, 50) if late else 0.0,
            "max": max(late) if late else 0.0,
        },
    }
