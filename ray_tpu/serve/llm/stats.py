"""Plain-int hot-path counters for the continuous-batching LLM engine.

Same pattern as ``rpc.WIRE`` / ``lease_manager.LEASE_STATS``: the scheduler
loop bumps plain ints (no instrument lock per decode step); a flush-time
collector in ``_private/self_metrics.py`` folds them into the
``ray_tpu_serve_llm_*`` instruments. Gauge-shaped state (running sequences,
admission queue depth, KV-block utilization) is NOT mirrored here — the
collector computes it at flush time by summing over ``ENGINES``, the
registry of engines whose scheduler loop is still running, so several
engines in one process fold into one honest series and the gauges drop to
zero when the last engine exits instead of freezing at their final values.

Spans (ISSUE 24) say where an iteration's and a request's time goes. Always
on and fixed size, the same bargain as the plain ints. One ``with
spans.span(name)`` per phase of the scheduler loop does both things a span
needs: it enters a ``jax.profiler.TraceAnnotation`` (inert unless a profile
is being taken; then the span lands in the ``/host:CPU`` plane of the same
``.xplane.pb`` as the device's programs, on the profiler's clock, and there
is no other way onto that clock) and it stamps ``time.monotonic_ns()`` at
both ends into the iteration's record, which leaves the process through
``LLMDeployment.get_stats()["spans"]``. OBSERVABILITY.md, "serve.llm spans",
has the table of names and arguments.

A token's way back (ISSUE 38) is on the same clock. The scheduler's ``llm.emit``
span gives every token it emits its own start as ``t_emit_ns`` (one clock read
a pass, carried with the id through the request's queue); the stream's pump
thread, the replica's ``next_stream_chunks`` and the proxy's event loop stamp the
CHUNK that carries it (``serve/_private/replica.py::CHUNK_STAMPS``; the proxy's
two last stamps ride back on the next poll that names the stream), and
``LLMDeployment`` joins the two into one ``DELIVERY_FIELDS`` record a token, in
the ``deliveries`` ring. A poll fetches the chunks of all its proxy's streams
at once and stamps ``t_enter_ns`` once, so the records that share a
``t_enter_ns`` are the tokens one poll carried; the ring counts them
(``polls``, ``batches``). Collector pauses, which stall every stream at once, are
on the record too: one ``gc.callbacks`` hook a process (``listen_for_gc``)
keeps the generation-2 collections in a ring and the younger ones as plain
ints. Both leave through ``get_stats()["spans"]`` only.

A replica's START (ISSUE 52) is on the record too, written once: one
``STAGE_FIELDS`` record a stage (``stage``: wall, the thread's and the
process's CPU time, the collector's share), from the deployment's first line
through every program ``LLMEngine._build_programs`` traces, lowers, compiles
and first runs; the seven seconds of ``setup`` are computed from them
(``setup_seconds``). Compilations are split three ways by what the installed
jax (0.9) raises on the compiling thread: a persistent-cache HIT raises
``cache_retrieval`` and then, around it, ``backend_compile`` with the read's
duration; a compile that is WRITTEN to the cache (a miss of an entry the next
start reads) raises the plain event ``cache_misses`` and then
``backend_compile``; a program the cache never holds (compiled in less than
``jax_persistent_cache_min_compile_time_secs``, or no cache) raises
``backend_compile`` alone. The ring's ``afresh`` column is 1 for the second
kind, and ``COMPILE_TOTALS`` counts all of it in plain ints no ring overflows.
"""

from __future__ import annotations

import array
import collections
import contextlib
import gc
import itertools
import logging
import os
import sys
import threading
import time
import weakref

logger = logging.getLogger(__name__)

# Engines register here at construction; the scheduler loop's exit (stop or
# crash) withdraws them. WeakSet so an abandoned engine can't pin itself.
ENGINES: "weakref.WeakSet" = weakref.WeakSet()

SPAN_NAMES = (
    "llm.iteration",
    "llm.admit",
    "llm.prefill.build",
    "llm.prefill.dispatch",
    "llm.prefill.fetch",
    "llm.decode.build",
    "llm.decode.dispatch",
    "llm.decode.fetch",
    "llm.sample",
    "llm.emit",
)
ITERATION_KINDS = ("decode", "prefill", "mixed")
# One record per pass of the scheduler loop that dispatched a program: the
# start stamp, what the pass carried, and the nanoseconds of each span; behind
# them what a pass of generation by diffusion over blocks did (0 in every other
# engine's records): the rows of it that committed a block, and the positions
# it transferred. ``rows`` is the rows FED; the tokens a pass yields are these.
ITERATION_FIELDS = (
    "t_start_ns", "rows", "prefill_tokens", "waiting", "running", "view_blocks", "context_tokens",
    "window_tokens", "chunk_tokens", "chunk_context_tokens",
) + SPAN_NAMES + ("block_commits", "tokens_unmasked")
(
    _T_START, _ROWS, _PREFILL_TOKENS, _WAITING, _RUNNING, _VIEW_BLOCKS, _CONTEXT_TOKENS, _WINDOW_TOKENS,
    _CHUNK_TOKENS, _CHUNK_CONTEXT_TOKENS,
) = range(10)
_FIRST_SPAN = _CHUNK_CONTEXT_TOKENS + 1
_BLOCK_COMMITS, _TOKENS_UNMASKED = _FIRST_SPAN + len(SPAN_NAMES), _FIRST_SPAN + len(SPAN_NAMES) + 1
_SPAN_FIELD = {name: _FIRST_SPAN + i for i, name in enumerate(SPAN_NAMES)}
# One record per request that ended; stamps are CLOCK_MONOTONIC nanoseconds
# (0 = never reached), a clock every process of a host shares.
REQUEST_FIELDS = (
    "rid", "request_id", "trace_id", "span_id",
    "t_recv_ns", "t_submit_ns", "t_admit_ns", "t_first_ns", "t_done_ns",
    "prompt_tokens", "cached_tokens", "generated", "preemptions", "outcome",
)
# ``afresh``: 1 on a ``backend_compile`` record whose program was compiled and
# written to the persistent cache, an entry a warm start would have read.
COMPILE_FIELDS = ("t_end_ns", "duration_ns", "event", "program", "afresh")
# One record per stage of a replica's start (``stage``): the stage's and, for a
# stage of one program's build, the program's name; the name of the thread that
# ran it; its two ends on CLOCK_MONOTONIC; the CPU time of that thread and of
# the whole process between them (wall less ``cpu_ns``: the thread did not
# run; ``process_cpu_ns`` less ``cpu_ns``: what the other threads burned
# meanwhile); the collector's nanoseconds (all generations) and its
# generation-2 collections inside the stage, process-wide.
STAGE_FIELDS = (
    "stage", "program", "thread", "t_start_ns", "t_end_ns", "cpu_ns", "process_cpu_ns", "gc_ns", "gc2",
)
_S_STAGE, _S_PROGRAM, _S_START, _S_END = (STAGE_FIELDS.index(f) for f in ("stage", "program", "t_start_ns", "t_end_ns"))
# One record per streamed token, all ints: the number N of the request ring's
# ``rid`` "llm-N", the token's index among those the request streamed, then
# seven CLOCK_MONOTONIC stamps in the order they are taken on a token's way
# from the scheduler to the socket (0 = not taken: the proxy's two last stamps
# of a stream's last batch, every proxy stamp of a proxy on another host).
DELIVERY_FIELDS = (
    "rid", "index",
    "t_emit_ns", "t_yield_ns", "t_asked_ns", "t_enter_ns", "t_sweep_ns", "t_got_ns", "t_wrote_ns",
)
_D_SWEEP = DELIVERY_FIELDS.index("t_sweep_ns")
_D_ENTER = DELIVERY_FIELDS.index("t_enter_ns")
_D_PROXY = tuple(DELIVERY_FIELDS.index(f) for f in ("t_asked_ns", "t_got_ns", "t_wrote_ns"))
# One record per generation-2 collection of this process.
GC_FIELDS = ("t_start_ns", "duration_ns", "collected")
ITERATION_RING, REQUEST_RING, COMPILE_RING, GC_RING = 2048, 512, 256, 256
# 20 s of the fastest cell's 1600 tokens a second; 2.4 MB of int64.
DELIVERY_RING = 32768
# A stamp further than this from its neighbour is another host's clock
# (util.tracing.hop_trace_events draws the same line).
FOREIGN_STAMP_S = 600.0
_FOREIGN_NS = int(FOREIGN_STAMP_S * 1e9)


class _LLMStats:
    __slots__ = (
        "admitted",
        "finished",
        "cancelled",
        "preemptions",
        "prefix_hit_blocks",
        "prefix_miss_blocks",
        "evicted_blocks",
        # Disaggregated serving (ISSUE 20): completed prefill→decode KV
        # handoffs counted on the IMPORTING (decode) side, exports sealed on
        # the prefill side, and cluster-prefix-tier import attempts by
        # outcome (hit = payload landed, miss = no registry row / local
        # cache already covered it, error = row existed but the fetch
        # failed: holder dead, payload evicted, or mailbox timeout).
        "handoffs",
        "handoff_exports",
        "prefix_import_hits",
        "prefix_import_misses",
        "prefix_import_errors",
        # What the fixed prefill chunk carried, real tokens and the padding
        # behind a prompt's last ones; admissions that made a slot's recurrent
        # state start from zero (linear-attention layers).
        "chunk_tokens_valid",
        "chunk_tokens_padded",
        "state_resets",
        # Assignments of tokens to routed experts, as the engines last read
        # them from the device: to experts the program holds, and (a program
        # that holds a share of them: ``TransformerConfig.expert_share``) to the others;
        # and (a router with identity experts: ``zero_experts``) the picks that were identities.
        "moe_assignments_held",
        "moe_assignments_elsewhere",
        "moe_picks_identity",
        # Scheduler-loop nanoseconds by span and iterations by kind: lists
        # of plain ints, indexed like SPAN_NAMES / ITERATION_KINDS.
        "span_ns",
        "iterations",
    )

    def __init__(self):
        for name in self.__slots__:
            setattr(self, name, 0)
        self.span_ns = [0] * len(SPAN_NAMES)
        self.iterations = [0] * len(ITERATION_KINDS)


LLM = _LLMStats()


class Ring:
    """Overwrite-oldest ring of fixed size. One writer at a time; a reader
    takes a best-effort snapshot (a slot store is atomic, so it never sees
    a torn record, at worst one the writer has just replaced)."""

    __slots__ = ("slots", "n")

    def __init__(self, size: int):
        self.slots: list = [None] * size
        self.n = 0  # records ever pushed

    def push(self, rec):
        self.slots[self.n % len(self.slots)] = rec
        self.n += 1

    def since(self, cursor: int = 0) -> list:
        """Records pushed at positions >= ``cursor`` that are still held,
        oldest first."""
        n, size = self.n, len(self.slots)
        return [self.slots[i % size] for i in range(max(cursor, n - size), n)]


# -- compilations: the listener is the process's, like the jax registry it
# hangs on, so every engine of a process reports the same ring ------------

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"
COMPILES = Ring(COMPILE_RING)
# [count, nanoseconds] of each event since the listener's start, and of the
# ``backend_compile`` events that were ``afresh``: what the ring drops, these keep.
COMPILE_TOTALS = {"backend_compile": [0, 0], "cache_retrieval": [0, 0], "compiled_afresh": [0, 0]}
_compile_lock = threading.Lock()  # any thread may compile
_cache_writes: set = set()  # threads whose compile in flight was written to the persistent cache
_listening = False


def _on_cache_write(event: str, **kwargs):
    if event == _CACHE_WRITE_EVENT:
        with _compile_lock:
            _cache_writes.add(threading.get_ident())


def _on_compile_event(event: str, duration_secs: float, **kwargs):
    kind = _COMPILE_EVENTS.get(event)
    if kind is not None:
        ns = int(duration_secs * 1e9)
        with _compile_lock:
            # The write's event comes on the compiling thread, inside the compile's.
            afresh = int(kind == "backend_compile" and threading.get_ident() in _cache_writes)
            if kind == "backend_compile":
                _cache_writes.discard(threading.get_ident())
            COMPILES.push((time.monotonic_ns(), ns, kind, str(kwargs.get("fun_name", "")), afresh))
            for total in (kind,) + (("compiled_afresh",) if afresh else ()):
                COMPILE_TOTALS[total][0] += 1
                COMPILE_TOTALS[total][1] += ns


def listen_for_compiles():
    """Once per process, before its first program is built: every program
    the backend builds (``backend_compile``; a persistent-cache hit raises it
    too, around the read and with the read's duration), every read of the
    persistent cache (``cache_retrieval``, raised before the hit's
    ``backend_compile`` on the same thread) and, as ``afresh`` on its
    ``backend_compile`` record, every write to it (the plain event
    ``cache_misses``: jax 0.9 raises it only where it writes the entry),
    stamped as it ends. The counter behind "which step recompiled",
    "compilations inside the window: there should be none" and "was this
    warm start warm"."""
    global _listening
    with _compile_lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    jax.monitoring.register_event_listener(_on_cache_write)


def compile_records() -> list:
    with _compile_lock:
        return [list(r) for r in COMPILES.since()]


def compile_totals() -> dict:
    with _compile_lock:
        return {k: list(v) for k, v in COMPILE_TOTALS.items()}


# -- collector pauses: the hook is the process's, like the collector. A
# collection holds the GIL from whichever thread tripped it and stalls the
# scheduler and every stream's delivery at once -----------------------------

GC_PAUSES = Ring(GC_RING)  # generation 2, GC_FIELDS
# Generations 0 and 1, from the hook's installation: how many, and their nanoseconds.
GC_YOUNGER = {"collections": [0, 0], "ns": [0, 0]}
_GC2 = [0, 0]  # generation 2 likewise: what the ring holds record by record, as two plain ints
_gc_annotation = None  # jax.profiler.TraceAnnotation once the hook is installed
_gc_open = None  # (t_start_ns, annotation or None) of the collection that is running


def _on_gc(phase: str, info: dict):
    # One collection runs at a time, start and stop on one thread with the
    # GIL held between them: a global carries the start, and the ring has
    # one writer.
    global _gc_open
    if phase == "start":
        ann = None
        if info["generation"] == 2:
            ann = _gc_annotation("gc.gen2")
            ann.__enter__()
        _gc_open = (time.monotonic_ns(), ann)
    elif _gc_open is not None:
        (t0, ann), _gc_open = _gc_open, None
        ns = time.monotonic_ns() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
            GC_PAUSES.push((t0, ns, info["collected"]))
            _GC2[0] += 1
            _GC2[1] += ns
        else:
            GC_YOUNGER["collections"][info["generation"]] += 1
            GC_YOUNGER["ns"][info["generation"]] += ns


def listen_for_gc():
    """Once per process, beside ``listen_for_compiles``: every generation-2
    collection into ``GC_PAUSES``, under a ``gc.gen2`` annotation from its
    start to its stop (in a profile it lies in the ``/host:CPU`` plane on
    the thread that tripped it), the younger generations into plain ints."""
    global _gc_annotation
    with _compile_lock:
        if _gc_annotation is not None:
            return
        from jax.profiler import TraceAnnotation

        _gc_annotation = TraceAnnotation
    gc.callbacks.append(_on_gc)


def gc_records() -> list:
    return [list(r) for r in GC_PAUSES.since()]


# -- a replica's start: one record a stage, written once ----------------------


def _gc_ns() -> int:
    return GC_YOUNGER["ns"][0] + GC_YOUNGER["ns"][1] + _GC2[1]


@contextlib.contextmanager
def stage(records: list, name: str, program: str = ""):
    """One stage of a start: appends its ``STAGE_FIELDS`` record to ``records``
    as it ends, whichever way, and says so in the log, so that a replica
    refused at Serve's limit leaves word of where it was. Under a
    ``TraceAnnotation`` where jax is loaded, as ``EngineSpans.span`` is: a
    profile taken over a start shows the stages beside the device's programs.
    For ``__init__``s and ``_build_programs``: nothing a token passes."""
    jax = sys.modules.get("jax")
    label = f"setup.{name} {program}" if program else f"setup.{name}"
    gc0, gc20 = _gc_ns(), _GC2[0]
    process0, cpu0, t0 = time.process_time_ns(), time.thread_time_ns(), time.monotonic_ns()
    try:
        with jax.profiler.TraceAnnotation(label) if jax is not None else contextlib.nullcontext():
            yield
    finally:
        t1, cpu, process = time.monotonic_ns(), time.thread_time_ns() - cpu0, time.process_time_ns() - process0
        records.append(
            (name, program, threading.current_thread().name, t0, t1, cpu, process, _gc_ns() - gc0, _GC2[0] - gc20)
        )
        logger.info("setup: %s %.1f s (cpu %.1f)", label[len("setup."):], (t1 - t0) / 1e9, cpu / 1e9)


def setup_seconds(stages: list) -> dict:
    """The seconds ``get_stats()["spans"]["setup"]`` has carried since PR 40,
    from the stage records: each ``__init__`` stage's wall under its own name;
    ``fused_build_s``, the trace and lowering of the step with a chunk;
    ``decode_build_s``, all the rest of the programs' build (its first start to
    its last end, less that)."""
    setup, built, fused_ns = {}, [], 0
    for rec in stages:
        if not rec[_S_PROGRAM]:
            setup[rec[_S_STAGE] + "_s"] = (rec[_S_END] - rec[_S_START]) / 1e9
            continue
        built.append(rec)
        if rec[_S_PROGRAM] == "decode_with_chunk" and rec[_S_STAGE] in ("trace", "lower"):
            fused_ns += rec[_S_END] - rec[_S_START]
    if built:
        wall_ns = max(rec[_S_END] for rec in built) - min(rec[_S_START] for rec in built)
        setup["decode_build_s"] = (wall_ns - fused_ns) / 1e9
    if fused_ns:
        setup["fused_build_s"] = fused_ns / 1e9
    return setup


def thread_cpu_ns() -> dict:
    """CPU nanoseconds of every thread of this process, ``{tid: (name,
    ns)}``: user and system time of ``/proc/self/task/<tid>/stat`` (in clock
    ticks: 10 ms) under the Python thread's name, or the kernel's (``comm``)
    for a thread Python did not start. Linux only; empty elsewhere."""
    try:
        tids = os.listdir("/proc/self/task")
        tick_ns = 10**9 // os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, AttributeError):
        return {}
    named = {t.native_id: t.name for t in threading.enumerate()}
    found = {}
    for tid in tids:
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                # "tid (comm) state ...": comm may hold spaces and brackets; utime and stime are fields 14 and 15
                comm, _, rest = f.read().partition("(")[2].rpartition(")")
        except OSError:
            continue  # gone meanwhile
        fields = rest.split()
        found[int(tid)] = (named.get(int(tid), comm), (int(fields[11]) + int(fields[12])) * tick_ns)
    return found


def busiest_threads(before: dict, after: dict, n: int = 5) -> list:
    """``[name, cpu_ns]`` of the ``n`` threads that burned most between two
    ``thread_cpu_ns()``: which thread a build waited for."""
    burned = [[name, ns - before.get(tid, ("", 0))[1]] for tid, (name, ns) in after.items()]
    return sorted((b for b in burned if b[1] > 0), key=lambda b: -b[1])[:n]


# -- deliveries: one record a streamed token ----------------------------------


class DeliveryRing:
    """Overwrite-oldest ring of ``DELIVERY_FIELDS`` records, packed into one
    array of int64: a record costs no object that outlives its push, and the
    export is a copy of bytes, whatever the ring holds (a list of 300,000 ints
    would cost the 1 Hz pollers tens of milliseconds to build and to pickle,
    and its allocations provoke the collections this module counts). Records
    arrive on the replica's actor-call threads, hence the lock.

    A push is one stream's batch of one poll, and a poll stamps its
    ``t_enter_ns`` once for all the streams it carried: ``n / polls`` is the
    tokens a poll carried, ``batches / polls`` the streams."""

    WIDTH = len(DELIVERY_FIELDS)

    def __init__(self, size: int = DELIVERY_RING):
        self.size = size
        self.n = 0  # records ever pushed
        self.batches = 0  # pushes that held a record
        self.polls = 0  # distinct t_enter_ns among them
        # The polls seen last: a poll's batches arrive as its streams' next
        # polls do, a few polls later at the most, and several proxies' polls
        # interleave.
        self._polls_seen: collections.deque = collections.deque(maxlen=64)
        self._slots = array.array("q", bytes(8 * self.WIDTH * size))
        self._lock = threading.Lock()

    def push(self, recs: list):
        """``recs``: tuples of ``DELIVERY_FIELDS``, in order of emission. A
        proxy stamp further than ``FOREIGN_STAMP_S`` from the replica's own is
        another host's clock: that record keeps no proxy stamp."""
        width, size, slots = self.WIDTH, self.size, self._slots
        packed = []
        for rec in recs:
            sweep = rec[_D_SWEEP]
            for i in _D_PROXY:
                if rec[i] and abs(rec[i] - sweep) > _FOREIGN_NS:
                    rec = [0 if j in _D_PROXY else v for j, v in enumerate(rec)]
                    break
            packed.append(array.array("q", rec))
        if not packed:
            return
        t_enter_ns = packed[0][_D_ENTER]
        with self._lock:
            for rec in packed:
                at = (self.n % size) * width
                slots[at:at + width] = rec
                self.n += 1
            self.batches += 1
            if t_enter_ns not in self._polls_seen:
                self._polls_seen.append(t_enter_ns)
                self.polls += 1

    def export(self) -> bytes:
        """The records still held, oldest first, as native int64, ``WIDTH`` to
        a record: ``array.array("q").frombytes`` reads them back."""
        row = 8 * self.WIDTH
        with self._lock, memoryview(self._slots).cast("B") as held:  # one copy, under the lock
            if self.n <= self.size:
                return bytes(held[: self.n * row])
            at = (self.n % self.size) * row
            return b"".join((held[at:], held[:at]))


# -- iterations and requests: one recorder per engine ----------------------

# Recorders outlive their scheduler loop (ENGINES does not): the /metrics
# collector still folds the requests that ended just before an engine stopped.
RECORDERS: "weakref.WeakSet" = weakref.WeakSet()


class _Span:
    __slots__ = ("_rec", "_field", "_ann", "t0")

    def __init__(self, rec: list, field: int, ann):
        self._rec, self._field, self._ann = rec, field, ann

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.monotonic_ns()
        return self

    def set(self, **args):
        """Arguments known only once the phase has run."""
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        self._rec[self._field] += time.monotonic_ns() - self.t0
        self._ann.__exit__(*exc)
        return False


class EngineSpans:
    """One engine's iteration, request and delivery records. The scheduler
    thread is the only writer of the iteration ring and the totals; the
    delivery ring takes its records from the replica's actor-call threads."""

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self.annotation = TraceAnnotation
        self.iterations = Ring(ITERATION_RING)
        self.requests = Ring(REQUEST_RING)
        self.deliveries = DeliveryRing()
        # A request complete on arrival ends on its submitting thread.
        self._request_lock = threading.Lock()
        self._cur = [0] * len(ITERATION_FIELDS)
        # Cumulative plain ints, for stats(); LLM carries the process's.
        self.span_ns = [0] * len(SPAN_NAMES)
        self.kinds = [0] * len(ITERATION_KINDS)
        self.requests_folded = 0  # the /metrics collector's cursor into ``requests``
        # The start, written once: STAGE_FIELDS records in order of their ends (the deployment puts its own
        # ahead), and the threads that burned most while the programs were built.
        self.stages: list = []
        self.build_threads: list = []
        RECORDERS.add(self)

    @property
    def setup(self) -> dict:
        """Seconds of each stage of building the replica and its engine (``setup_seconds``)."""
        return setup_seconds(self.stages)

    # -- one iteration (scheduler thread) -------------------------------

    def begin(self, waiting: int, running: int) -> _Span:
        """Opens ``llm.iteration``, the parent of the pass's other spans;
        ``end`` closes it."""
        cur = self._cur
        cur[:] = [0] * len(cur)
        cur[_WAITING], cur[_RUNNING] = waiting, running
        it = _Span(cur, _SPAN_FIELD["llm.iteration"], self.annotation("llm.iteration"))
        it.__enter__()
        cur[_T_START] = it.t0
        return it

    def span(self, name: str, **args) -> _Span:
        return _Span(self._cur, _SPAN_FIELD[name], self.annotation(name, **args))

    def carried(self, rows: int = 0, prefill_tokens: int = 0, view_blocks: int = 0,
                context_tokens: int = 0, window_tokens: int = 0, chunk_tokens: int = 0,
                chunk_context_tokens: int = 0, block_commits: int = 0, tokens_unmasked: int = 0):
        """What this pass dispatched: decode rows, the width in blocks of
        their step's block table, the tokens of context they hold between
        them (each row's length, the token fed included) and how many of
        those a layer with the model's sliding window may read (each row's
        length or the window, whichever is less); prompt tokens of its chunk,
        and how many of those rode inside the decode step it dispatched
        (``chunk_tokens``: all or none), and how many tokens the chunk's row
        held before it (``chunk_context_tokens``: what the chunk's attention
        reads beside the chunk itself, whatever the width of its view); of a
        pass over blocks (generation by diffusion), the rows that committed
        theirs and the positions the pass transferred."""
        self._cur[_BLOCK_COMMITS] += block_commits
        self._cur[_TOKENS_UNMASKED] += tokens_unmasked
        self._cur[_CHUNK_TOKENS] += chunk_tokens
        self._cur[_CHUNK_CONTEXT_TOKENS] += chunk_context_tokens
        self._cur[_ROWS] += rows
        self._cur[_PREFILL_TOKENS] += prefill_tokens
        self._cur[_VIEW_BLOCKS] += view_blocks
        self._cur[_CONTEXT_TOKENS] += context_tokens
        self._cur[_WINDOW_TOKENS] += window_tokens

    def end(self, it: _Span):
        """Closes the iteration. A pass that dispatched no program leaves no
        record: the idle wait is the time between records."""
        cur = self._cur
        rows, chunk = cur[_ROWS], cur[_PREFILL_TOKENS]
        it.set(rows=rows, prefill_tokens=chunk)
        it.__exit__(None, None, None)
        if not (rows or chunk):
            return
        self.iterations.push(tuple(cur))
        kind = ITERATION_KINDS.index("mixed" if rows and chunk else "decode" if rows else "prefill")
        self.kinds[kind] += 1
        LLM.iterations[kind] += 1
        for i in range(len(SPAN_NAMES)):
            ns = cur[_FIRST_SPAN + i]
            if ns:
                self.span_ns[i] += ns
                LLM.span_ns[i] += ns

    # -- one request ------------------------------------------------------

    def end_request(self, req, outcome: str):
        def ns(t):
            return round(t * 1e9) if t else 0  # int() cuts a proxy's stamp a nanosecond short now and then

        t_recv, t_submit = ns(req.t_recv), ns(req.t_submit)
        if abs(t_recv - t_submit) > FOREIGN_STAMP_S * 1e9:
            t_recv = 0  # a proxy on another host: not this clock
        rec = (
            req.id, req.request_id, req.trace_id, req.span_id,
            t_recv, t_submit, ns(req.t_admit), ns(req.t_first), ns(req.t_done),
            len(req.prompt), req.cached_tokens, len(req._sched_generated), req.preemptions, outcome,
        )
        with self._request_lock:
            self.requests.push(rec)

    # -- how it leaves the process -----------------------------------------

    def totals(self) -> dict:
        """For ``LLMEngine.stats()``: the cumulative plain ints only."""
        return {
            "span_ns": dict(zip(SPAN_NAMES, self.span_ns)),
            "iterations": dict(zip(ITERATION_KINDS, self.kinds)),
        }

    def export(self) -> dict:
        """For ``LLMDeployment.get_stats()["spans"]``: the rings as plain
        lists, oldest first, with the names of their columns. The iteration
        ring goes as ONE flat list of ints, row after row (a row is
        ``len(fields["iterations"])`` wide): two thousand small lists cost
        the 1 Hz pollers more to build, to pickle and to read (PERF.md, PR 24).
        The delivery ring goes as packed bytes (``DeliveryRing.export``)."""
        return {
            "iterations": list(itertools.chain.from_iterable(self.iterations.since())),
            "requests": [list(r) for r in self.requests.since()],
            "compiles": compile_records(),
            "deliveries": self.deliveries.export(),
            "gc": gc_records(),
            "gc_younger": {k: list(v) for k, v in GC_YOUNGER.items()},
            "setup": self.setup,
            "stages": [list(r) for r in self.stages],
            "build_threads": [list(t) for t in self.build_threads],
            "compile_totals": compile_totals(),
            "fields": {
                "iterations": list(ITERATION_FIELDS),
                "requests": list(REQUEST_FIELDS),
                "compiles": list(COMPILE_FIELDS),
                "deliveries": list(DELIVERY_FIELDS),
                "gc": list(GC_FIELDS),
                "stages": list(STAGE_FIELDS),
            },
        }
