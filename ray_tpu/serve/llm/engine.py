"""Continuous-batching LLM engine (ISSUE 11 tentpole).

``models/generate.py`` can prefill and decode a batch, but a replica built on
it serves one batch at a time: a request arriving mid-decode waits for the
whole batch to drain. This engine is the batching brain in between — the
vLLM-lineage iteration-level scheduler on top of the paged KV cache:

- **slots**: a fixed number of decode lanes (static [num_slots] shapes, so
  XLA compiles the decode step once per block-table width, below); a
  sequence occupies a slot from admission to completion, and a new prompt is
  admitted the moment a slot and enough KV blocks free up — mid-decode, not
  between batches.
- **a decode step is as wide as its longest row**: the decode program
  gathers and attends over the block table it is handed, whatever the rows
  hold, so a step's table is cut to the smallest rung of ``_view_rungs``
  (16, 32, 64, ... blocks, then ``n_max``) that covers the longest running
  row. One program a rung, every one built in ``__init__``: a replica that
  reports ready compiles nothing more. The prefill chunk keeps ``n_max``.
  A latent pool on a TPU, and a pool of one group of key and value leaves,
  has no ladder: its decode step walks each row's table to the row's length
  in a kernel (``ops/latent_attention.py``, ``ops/paged_attention.py``), so it
  is handed the whole table and there is one decode program
  (``generate.kernel_reads``; ``stats()["latent_kernel_steps"]`` /
  ``["kv_kernel_steps"]``). A latent pool's prefill chunk is read in place
  there too, each tile of its queries to its own position
  (``["latent_kernel_chunks"]``): the chunk keeps ``n_max`` and pays for what
  the row holds. A decode step that carries the pass's prefill
  chunk (``_shape_fuses``) is handed the whole table on every backend.
- **paged KV cache**: ``init_paged_cache`` block pool + per-sequence block
  tables with a host-side free-list. Block 0 is the reserved null block
  (inactive slots and write-masked padding rows land there). The pool is
  DONATED to the decode and prefill programs, which update it in place
  (``_run_donated``): one pool lives on the device, never a second copy.
- **chunked prefill interleaved with decode**: at most one fixed-shape
  prefill chunk runs per scheduler iteration between decode steps, so a
  long admitted prompt cannot stall tokens for running streams.
- **prefix cache**: full blocks covering the ORIGINAL prompt are registered
  under a chain hash (hash of block tokens + predecessor hash — exactly the
  causal dependency of the KV rows); a new request whose prompt shares the
  leading blocks reuses them by refcount and skips that part of prefill.
  refs-0 blocks stay cached and are evicted LRU under allocation pressure.
- **preemption**: when the pool is exhausted mid-decode the youngest
  running sequence is preempted RECOMPUTE-style — its blocks are released
  and it re-enters the wait queue; on re-admission its already-emitted
  tokens are teacher-forced through prefill (bit-identical continuation,
  nothing is ever re-emitted, the request's RNG stream is untouched).
- **the draw is inside the programs**: the decode and the prefill program
  end in ``models/generate.py::draw_tokens`` and hand back token ids, so a
  step copies ``[num_slots]`` int32 to the host and never ``[num_slots, V]``
  logits. A row's noise comes from ``fold_in(key(seed), counter)`` alone:
  the key is the request's 64-bit seed (its two uint32 halves are the
  threefry key), the counter is the index of the token drawn. Nothing is
  carried from draw to draw, so a token does not depend on the slot, the
  rows beside it, the program (a prompt's last chunk, a teacher-forced
  tail, a decode step) or the replica.
- **the decode loop runs one step ahead**: step N+1 is built and dispatched
  while step N is still on the device, its token column taken from step N's
  ids on the device, and only then are N's ids fetched and emitted
  (``_loop``, ``_decode_tick``). A request ends by count, so the host knows
  everything else of a row (position, counter, block table, whether the row
  exists) before N returns. One loop: with nothing in flight the tick
  dispatches from the host's tokens. What the spans cover now:
  ``llm.decode.build`` / ``llm.decode.dispatch`` the step a pass launches,
  ``llm.decode.fetch`` / ``llm.sample`` / ``llm.emit`` and the record's
  ``rows``, ``view_blocks``, ``context_tokens`` the step it lands;
  ``stats()`` counts ``decode_steps``, ``decode_steps_run_ahead`` and
  ``decode_rows_dropped``.
- **generation by diffusion over blocks** (``cfg.block_diffusion = B > 0``): a
  running row owns a BLOCK of B aligned positions, not a position. A pass feeds
  all B of every row (``generate.paged_decode_chunk_hidden`` at q = B under the
  mask "causal between blocks, two-way inside one"), writes their keys, values
  and expert choices over what the pass before left, draws every position and
  keeps (transfers) some of what it drew at positions still masked
  (``generate.transfer_block``); when none is masked one more pass, the COMMIT,
  feeds the final ids, and only then does the row's position move by B and are
  the block's tokens emitted, up to B of them into one stream under one
  ``llm.emit`` stamp. Rows admitted at different times are in different passes
  of their blocks inside one program. The schedule is static (pass s of
  ``denoising_steps`` transfers ``B // S`` (+ 1 for the first ``B mod S``)
  positions of largest confidence), so the host knows BY COUNT which pass of its
  block every row is in and when it commits, and the loop stays one pass ahead
  exactly as above: pass N + 1 takes its blocks from pass N's ``[num_slots, B]``
  output on the device (a row that committed in N starts from ``MASK``), and N
  is fetched and emitted after N + 1 is dispatched. Only the passes of a request
  that asked for them one by one (``submit(return_block_passes=True)``, the
  benchmark's check) are fetched before the next is dispatched
  (``stats()["block_passes_synced"]``). A schedule that moves a row by what a
  pass finds (every position over a confidence) needs "how far each row moved"
  decided on the device and is ROADMAP R7's. A prompt
  of n tokens is prefilled to the last block's edge, ``n - n mod B``; the rest
  starts its first block. A request ends by count of TOKENS, inside a block if
  need be: the block's leading tokens only are emitted. The record's ``rows`` stays
  the rows fed; ``block_commits`` and ``tokens_unmasked`` say what a pass did.
- **streaming**: each request carries a queue the scheduler feeds token by
  token; ``LLMRequest`` iterates it — the replica's ``StreamingResponse``
  pump drains that iterator straight onto the HTTP socket. Each id carries
  the start of the ``llm.emit`` span that emitted it
  (``LLMRequest.stamped``), the first of a token's seven stamps on its way
  to the socket (``stats.DELIVERY_FIELDS``).

Concurrency contract: all cache/free-list/slot state is owned by the
scheduler thread; ``submit``/``cancel`` only touch the wait queue under
``_lock`` and set the wake event (annotated ``@any_thread``); consumers
block only on per-request queues.
"""

from __future__ import annotations

import hashlib
import itertools
import queue as _queue
import threading
import time
from collections import OrderedDict, deque
from typing import Optional

import numpy as np

from ray_tpu._private import flight_recorder as _flight
from ray_tpu._private.concurrency import any_thread, blocking
from ray_tpu.serve.llm.stats import (
    ENGINES, LLM, EngineSpans, busiest_threads, listen_for_compiles, listen_for_gc, stage, thread_cpu_ns,
)
from ray_tpu.util import tracing


# Terminal-error sentinel for a DELIBERATE engine teardown (replica
# retiring). Requests that die with it surface the typed
# ReplicaDrainingError, which the serve proxy treats as migratable — a
# stream outliving its replica's drain window resumes elsewhere instead of
# dropping. Every other error string stays a plain RuntimeError.
SHUTDOWN_ERROR = "engine shutdown"


def _request_error(val: str) -> Exception:
    if val == SHUTDOWN_ERROR:
        from ray_tpu.exceptions import ReplicaDrainingError

        return ReplicaDrainingError(
            msg="llm engine shut down mid-request (replica retiring)"
        )
    return RuntimeError(val)


class LLMRequest:
    """One generation request: scheduler-fed token queue + terminal state.

    Iterate it for streaming (``for tok in req``), or ``result()`` to
    collect every token. The scheduler owns all ``_sched``-prefixed fields.
    """

    def __init__(self, rid, prompt, max_new_tokens, temperature, top_k, seed):
        self.id = rid
        # What the request's record (stats.REQUEST_FIELDS) keeps beside the
        # stamps: the proxy's identifier, the task span of the replica call
        # (under RAY_TPU_TRACING=1) that is the parent of the engine's stages.
        self.request_id = ""
        self.trace_id = ""
        self.span_id = ""
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # The request's sampling randomness is a COUNTER-BASED stream: token
        # i is drawn inside the program from
        # fold_in(threefry key (seed >> 32, seed & 0xFFFFFFFF), i), never from
        # carried or split RNG state. That makes the stream
        # position-addressable, so a request resumed on ANOTHER replica with
        # resume_tokens= (mid-stream migration) continues bit-identically:
        # exactly like recompute preemption, which never left the process.
        self.seed = int(seed) & 0xFFFFFFFFFFFFFFFF
        # What every dispatch carries of this request's draw, as the int32
        # columns _ROW_DRAW of a program's row: temperature's float32 bits,
        # top_k, the seed's low and high half (the counter is the fifth).
        self._sched_draw = np.array(
            [
                np.float32(self.temperature).view(np.uint32),
                min(max(self.top_k, 0), 2**31 - 1),
                self.seed & 0xFFFFFFFF,
                self.seed >> 32,
            ],
            np.uint32,
        ).view(np.int32)
        self.cancelled = threading.Event()
        self.error: Optional[str] = None
        # Prefill-role terminal state: the sealed-KV handoff descriptor
        # (dict) a decode-pool replica continues from; None on engines that
        # decode their own requests.
        self.handoff: Optional[dict] = None
        # submit(return_routed_experts=True): filled as the request completes.
        self.return_routed_experts = False
        self.routed_experts: Optional[np.ndarray] = None
        # submit(return_state=True): filled as the request completes.
        self.return_state = False
        self.state: Optional[np.ndarray] = None
        # submit(return_block_passes=True) (generation by diffusion over blocks): one record a pass of each block.
        self.return_block_passes = False
        self.block_passes: list = []
        self.t_recv: Optional[float] = None  # the proxy's stamp, same clock
        self.t_submit = time.monotonic()
        self.t_admit: Optional[float] = None  # first admission
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self._q: _queue.Queue = _queue.Queue()
        self._finished = False  # scheduler-side guard: one terminal event
        self.cached_tokens = 0  # prompt tokens the first admission did not recompute
        self.preemptions = 0
        # --- scheduler-owned ---
        self._sched_generated: list[int] = []
        self._sched_state = "waiting"  # waiting | prefill | decode | done
        self._sched_slot: Optional[int] = None
        self._sched_table: list[int] = []
        self._sched_pos = 0
        self._sched_target = 0
        self._sched_cached_bids: set[int] = set()
        self._sched_registered_bids: set[int] = set()
        self._sched_hashes: list[bytes] = []
        self._sched_admit_seq = -1
        # Generation by diffusion over blocks (``LLMEngine._block_begin``), all AS DISPATCHED, a pass ahead of what
        # has landed: the start of the row's block, the index of its next denoising pass (0: the pass takes the block
        # from the host, its known tokens and MASK; later ones from the pass before on the device), the positions still
        # masked, and the tokens the request will have emitted once every dispatched pass has landed.
        self._sched_bstart = 0
        self._sched_bpass = 0
        self._sched_bmasks = 0
        self._sched_emit_ahead = 0
        # Fetched KV import awaiting admission-time scatter: (host payload
        # [2, L, n_blocks, Bs, KV, Dh], kv_pos tokens it covers). Set by the
        # SUBMIT thread (the network pull must not stall the scheduler);
        # consumed and dropped by _admit.
        self._sched_kv_import: Optional[tuple] = None

    @property
    def num_generated(self) -> int:
        return len(self._sched_generated)

    @blocking
    def __iter__(self):
        for tok, _ in self.stamped():
            yield tok

    @blocking
    def stamped(self):
        """The stream as ``(token, t_emit_ns)``: each id with the start of the
        ``llm.emit`` span that emitted it, the scheduler's one stamp a pass
        (``time.monotonic_ns()``; stats.DELIVERY_FIELDS)."""
        while True:
            kind, val = self._q.get()
            if kind == "token":
                yield val
            elif kind == "done":
                return
            elif kind == "handoff":
                self.handoff = val
                return
            else:  # error
                raise _request_error(val)

    @blocking
    def result(self, timeout: float = 120.0) -> list[int]:
        """Collect the full completion (raises on engine-side error)."""
        out: list[int] = []
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"request {self.id} not finished in {timeout}s")
            try:
                kind, val = self._q.get(timeout=min(remaining, 1.0))
            except _queue.Empty:
                continue
            if kind == "token":
                out.append(val[0])
            elif kind == "done":
                return out
            elif kind == "handoff":
                # Prefill-role terminal: the first sampled token travels
                # inside the descriptor (resume_tokens on the decode side),
                # so the caller reads ``req.handoff``, not the token list.
                self.handoff = val
                return out
            else:
                raise _request_error(val)


def block_hashes(tokens, block_size: int) -> list[bytes]:
    """Chain hash per FULL block: h_i = sha1(h_{i-1} || tokens of block i).
    The KV rows of block i depend (causally) on every token up to its end,
    so the chain is exactly the reuse key."""
    out: list[bytes] = []
    h = b""
    for i in range(len(tokens) // block_size):
        blk = np.asarray(
            tokens[i * block_size : (i + 1) * block_size], dtype=">u4"
        ).tobytes()
        h = hashlib.sha1(h + blk).digest()
        out.append(h)
    return out


def prefix_route_hint(tokens, block_size: int = 16) -> str:
    """Router affinity hint for cache-aware routing: the hash of the FIRST
    full block (shared system prompts share it; suffixes don't disturb it).
    Empty string when the prompt doesn't fill one block — no affinity."""
    hs = block_hashes(list(tokens)[:block_size], block_size)
    return hs[0].hex() if hs else ""


# One int32 row per sequence is everything a program is told about it beside
# the chunk's tokens: ONE host array a dispatch (each `jnp.asarray` is ~0.1 ms
# of the gap between two steps), the block table behind a fixed head.
_ROW_TOKEN = 0  # decode: the token fed, or _ID_IN_FLIGHT
_ROW_VALID_TO = 0  # prefill (its tokens are an argument of their own): the teacher-forced target
_ROW_POS = 1  # position of the first token fed
_ROW_DRAW = slice(2, 6)  # LLMRequest._sched_draw
_ROW_COUNTER = 6  # index of the token this dispatch draws
# The block table from here on: n_max wide (prefill), a rung of _view_rungs
# (decode). Under a layer pattern the row's ring in the window layers' group
# comes first (``LLMEngine.ring_blocks`` wide); with layers that keep a recurrent
# state a slot (linear attention, Mamba-2: ``"state"`` in ``generate.pool_reach``)
# ``_STATE_COLS`` columns, a prefill row's slot in their group and whether its
# state starts from zero (a decode row IS its slot's: row b, slot b); then the
# table.
_ROW_TABLE = 7
_STATE_COLS = 2

# In a decode row's token column: the token is the id the step before drew for
# this slot, still on the device (``decode``'s ``ids`` argument).
_ID_IN_FLIGHT = -1

# Generation by diffusion over blocks: a block pass's row carries, between the
# head and the table, its block's ``cfg.block_diffusion`` ids: a token's id,
# ``generate.BLOCK_MASKED`` (-1) for a position still masked, or this one in
# every column for "the block as the pass before left it", still on the device
# (``block_pass``'s ``carry`` argument). Column ``_ROW_TOKEN`` holds how many
# positions the pass transfers (0: a commit pass), ``_ROW_POS`` the block's
# start, ``_ROW_COUNTER`` ``(start - prompt length) * B + the pass's index``: the
# noise of position j is ``fold_in(key(seed), (its token's index) * B + pass)``.
_BLOCK_CARRIED = -2

# In a decode row's token column of a step that carries a prompt's LAST chunk,
# on the (otherwise inactive) row of the prefilling request's slot: the id the
# step returns for this slot is the one drawn from the chunk, the request's
# first, so that the step after feeds it from the device like any other.
_ID_FROM_CHUNK = -2

# The narrowest block table a decode step is given, in blocks. An engine whose
# n_max is no wider has one decode program.
_MIN_VIEW_BLOCKS = 16

# Rows of one tile of the matrix unit. A decode step carries the pass's prefill
# chunk (``_compiled_fns``' third program) only where its rows and the chunk's
# fill no more than one: that is where the chip showed the chunk's rows to
# ride for nothing on the step's read of the weights (PERF.md, PR 39 and 40).
_MXU_TILE_ROWS = 128


def _with_room(fn):
    """``fn()``, called from a frame so large that the interpreter gives it a
    chunk of its thread's data stack to itself, with ~190 KB left in it for
    every frame under it. CPython 3.11+ keeps a thread's frames in chunks of
    16 KB and FREES a chunk as its first frame returns: a call that happens to
    start a chunk maps and unmaps it every time it is made, ~8 us where a call
    takes 0.1. Where the boundary falls follows the size of every frame below,
    so tracing and lowering, which make millions of calls 50-150 frames deep,
    ran up to twice as long in a replica as alone, and seconds longer or
    shorter with a key more in a dict literal of ``__init__`` (PERF.md, PR 49
    and PR 52). No boundary is in reach of a build that starts from here."""
    return fn()


# 40,000 words of evaluation stack the function never uses: a 320 KB frame, so a 512 KB chunk.
_with_room.__code__ = _with_room.__code__.replace(co_stacksize=40_000)


def _view_rungs(n_max: int) -> tuple:
    """Widths in blocks of the decode step's block table, ascending:
    ``_MIN_VIEW_BLOCKS`` doubled while below ``n_max``, then ``n_max``."""
    rungs, w = [], _MIN_VIEW_BLOCKS
    while w < n_max:
        rungs.append(w)
        w *= 2
    return (*rungs, n_max)


def _draw_row_tokens(logits, rows, **how):
    """``draw_tokens`` from the draw columns of the programs' int32 rows (``how``: its keywords)."""
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.models.generate import draw_tokens

    draw = rows[:, _ROW_DRAW]
    return draw_tokens(
        logits,
        lax.bitcast_convert_type(draw[:, 0], jnp.float32),
        draw[:, 1],
        lax.bitcast_convert_type(draw[:, 2:4], jnp.uint32),
        rows[:, _ROW_COUNTER],
        **how,
    )


# Process-level compiled-program cache: engines with the same model config
# share the jitted decode/prefill callables, so jax's own shape-keyed cache
# applies across engine instances (tests and replica reconfigures would
# otherwise recompile identical programs behind fresh lambdas).
_JIT_CACHE: dict = {}
_JIT_LOCK = threading.Lock()


def _compiled_fns(cfg, ring: int = 0):
    """(decode, prefill, decode_with_chunk): ``decode(params, rows
    [num_slots, 7 + w], pool, ids [num_slots])``, ``w`` a rung of
    ``_view_rungs`` (one compiled program each) and ``ids`` what the step
    before returned (a row whose token column is ``_ID_IN_FLIGHT`` feeds its
    slot's), and ``prefill(params, tokens [1, q], pool, rows [1, 7 + n_max])``,
    both ``-> (token ids int32, one a row, pool)``. ``ring`` (a layer pattern
    only): blocks of a row's ring, which a row carries ahead of its table
    (``7 + ring + w`` columns). With layers that keep a state a slot ``_STATE_COLS``
    columns, a row's slot and whether its state starts from zero, also lie
    ahead of the table; only the prefill program reads them.

    ``decode_with_chunk(params, rows [num_slots, 7 + n_max], pool, ids [num_slots],
    tokens [1, q], chunk_rows [1, 7 + n_max]) -> (ids [num_slots], pool)`` is the
    decode step that carries the pass's prefill chunk: both go through the
    layers as ``num_slots + q`` rows of one matmul chain
    (``generate.paged_decode_step_with_chunk``), each part over its own table,
    the head is projected for
    ``num_slots + 1`` rows and all of them drawn in the program. The chunk's
    id is the first token of its request if the chunk is the prompt's last: it
    is returned in the slot of the row whose token column says
    ``_ID_FROM_CHUNK`` (the request's own, inactive as a decode row until then),
    so the ids have the decode program's shape and the next step feeds them
    the same way. For a pool of one group of key and value leaves; the engine
    builds it for no other (``LLMEngine._shape_fuses``)."""
    with _JIT_LOCK:
        fns = _JIT_CACHE.get((cfg, ring))
        if fns is None:
            import jax
            import jax.numpy as jnp

            from ray_tpu.models.generate import (
                last_row_logits,
                paged_decode_chunk_hidden,
                paged_decode_step,
                paged_decode_step_with_chunk,
                pool_reach,
            )
            from ray_tpu.models.transformer import _logits

            # What lies ahead of a row's block table, by how the pool's groups are
            # reached: a ring's blocks, then the state's columns (only a chunk reads them).
            reach = pool_reach(cfg)
            state_at = _ROW_TABLE + ring
            table_at = state_at + (_STATE_COLS if "state" in reach else 0)

            def tables(rows, chunk=False):
                cut = {}
                if "state" in reach and chunk:
                    cut.update(state_slots=rows[:, state_at], state_fresh=rows[:, state_at + 1] != 0)
                cut["block_tables"] = rows[:, table_at:]
                if "ring" in reach:
                    cut["ring_tables"] = rows[:, _ROW_TABLE:state_at]
                return cut

            def decode_rows(p, rows, c, ids):
                fed = rows[:, _ROW_TOKEN]
                fed = jnp.where(fed == _ID_IN_FLIGHT, ids, fed)
                logits, c = paged_decode_step(p, fed, c, **tables(rows), pos=rows[:, _ROW_POS], cfg=cfg)
                return _draw_row_tokens(logits, rows), c

            def prefill_chunk_row(p, t, c, rows):
                # Chunked prefill consumes logits for at most ONE row (the
                # prompt's last real token, on its final chunk) — project
                # just that row instead of paying the [1, q, V] head matmul
                # per chunk (the row is traced: no recompile per position).
                pos, valid_to = rows[:, _ROW_POS], rows[:, _ROW_VALID_TO]
                x, c = paged_decode_chunk_hidden(p, t, c, pos=pos, cfg=cfg, valid_to=valid_to, **tables(rows, chunk=True))
                row = jnp.clip(valid_to - 1 - pos, 0, t.shape[1] - 1)
                return _draw_row_tokens(last_row_logits(p, x, row), rows), c

            def decode_rows_with_chunk(p, rows, c, ids, t, chunk_rows):
                S = rows.shape[0]
                fed = rows[:, _ROW_TOKEN]
                from_chunk = fed == _ID_FROM_CHUNK
                fed = jnp.where(fed == _ID_IN_FLIGHT, ids, jnp.where(from_chunk, 0, fed))
                pos, valid_to = chunk_rows[:, _ROW_POS], chunk_rows[:, _ROW_VALID_TO]
                x, c = paged_decode_step_with_chunk(
                    p, fed, t, c, rows[:, _ROW_TABLE:], rows[:, _ROW_POS],
                    chunk_rows[:, _ROW_TABLE:], pos, valid_to, cfg,
                )
                last = S + jnp.clip(valid_to - 1 - pos, 0, t.shape[1] - 1)
                heads = jnp.concatenate([x[:S], x[last]])  # the head for num_slots + 1 rows, not + q
                drawn = _draw_row_tokens(_logits(p, heads), jnp.concatenate([rows, chunk_rows]))
                return jnp.where(from_chunk, drawn[S], drawn[:S]), c

            # The pool (argument 2) is DONATED to every program; the layer
            # scan carries it, so a step updates it in place (the caller's
            # side of the bargain: ``LLMEngine._run_donated``). The names (a
            # lambda, prefill_chunk_row) are how the benchmark's
            # trace_programs patterns find the programs in a trace: keep them.
            # The step that carries a chunk is a lambda too: it IS a decode
            # step, and in a trace it is found and timed as one.
            fns = (
                jax.jit(lambda p, rows, c, ids: decode_rows(p, rows, c, ids), donate_argnums=2),
                jax.jit(prefill_chunk_row, donate_argnums=2),
                jax.jit(lambda p, rows, c, ids, t, chunk: decode_rows_with_chunk(p, rows, c, ids, t, chunk), donate_argnums=2),
            )
            _JIT_CACHE[(cfg, ring)] = fns
        return fns


def _block_pass_fn(cfg):
    """``block_pass(params, rows [num_slots, 7 + B + w], pool, carry [num_slots, B])
    -> (blocks [num_slots, B] int32, pool)``: one pass of generation by
    diffusion over blocks, every row's block of ``B = cfg.block_diffusion``
    positions at once (the row's columns: ``_BLOCK_CARRIED``). The program ends
    in the draw (scope ``block_draw``): the head over all ``num_slots * B`` rows,
    ``draw_tokens`` with each position's own noise, the confidence, and the
    transfer by each row's count (``generate.transfer_block``). The host fetches the
    blocks, ``generate.BLOCK_MASKED`` where a position is still masked, and
    never logits."""
    with _JIT_LOCK:
        fn = _JIT_CACHE.get((cfg, "block"))
        if fn is None:
            import jax
            import jax.numpy as jnp

            from ray_tpu.models.generate import paged_decode_chunk_hidden, transfer_block
            from ray_tpu.models.transformer import _logits

            B = cfg.block_diffusion
            table_at = _ROW_TABLE + B

            def block_pass(p, rows, c, carry):
                S = rows.shape[0]
                given = rows[:, _ROW_TABLE:table_at]
                ids = jnp.where(given == _BLOCK_CARRIED, carry, given)
                fed = jnp.where(ids < 0, cfg.mask_token_id, ids)
                x, c = paged_decode_chunk_hidden(p, fed, c, rows[:, table_at:], rows[:, _ROW_POS], cfg, step=True)
                with jax.named_scope("block_draw"):
                    logits = _logits(p, x.reshape(S * B, -1))
                    # A row's head once a position, each with its own counter (under 0: a prompt's token inside the first block, never drawn).
                    counter = rows[:, _ROW_COUNTER, None] + B * jnp.arange(B, dtype=jnp.int32)[None]
                    heads = jnp.repeat(rows[:, :_ROW_TABLE], B, axis=0).at[:, _ROW_COUNTER].set(jnp.maximum(counter.reshape(-1), 0))
                    drawn, confidence = _draw_row_tokens(logits, heads, with_prob=True)
                    return transfer_block(ids, drawn.reshape(S, B), confidence.reshape(S, B), rows[:, _ROW_TOKEN]), c

            # The name is how the benchmark's trace_programs pattern finds the pass in a trace: keep it.
            fn = jax.jit(block_pass, donate_argnums=2)
            _JIT_CACHE[(cfg, "block")] = fn
        return fn


# The transfer plane's payload is [2, L, blocks, Bs, KV, Dh]: keys and values
# (kv_transfer.seal_kv_payload, LLMEngine._scatter_import; ROADMAP D5).
_LATENT_POOL_KV_PAYLOAD = (
    "{what} needs the KV transfer plane, whose payload layout is keys and values; "
    "a latent-attention pool holds one latent row a token (kv_transfer.py, ROADMAP D5)"
)
# And of ONE group of layers. Under a layer pattern a prefix is more than its
# blocks: the window layers' rows of its last ``sliding_window`` tokens live in
# the ring of the request that computed them and go when it ends.
_PATTERN_POOL_KV_PAYLOAD = (
    "{what} needs the KV transfer plane, whose payload is one group of layers' blocks; "
    "under a layer pattern (layer_kinds) the window layers keep a ring a request, "
    "which no block table names (kv_transfer.py, ROADMAP R3)"
)
# And of layers that hold a token's rows at all. A linear-attention layer keeps
# one recurrent state a slot: a prefix is its blocks AND the state after its
# last token, which nothing snapshots.
_STATE_POOL_KV_PAYLOAD = (
    "{what} needs the KV transfer plane, whose payload is blocks of keys and values; "
    "linear-attention layers (layer_kinds has 'linear') keep a recurrent state a slot, "
    "which no block holds and nothing snapshots at a block's boundary (kv_transfer.py, ROADMAP R5)"
)


# The same of a Mamba-2 block's state, and of the blocks around it.
_SSM_POOL_KV_PAYLOAD = (
    "{what} needs the KV transfer plane, whose payload is blocks of keys and values; "
    "Mamba-2 state-space blocks (layer_kinds has 'mamba') keep a recurrent state a slot, "
    "which no block holds and nothing snapshots at a block's boundary (kv_transfer.py, ROADMAP R5)"
)


# And of a gated short convolution's carried rows.
_CONV_POOL_KV_PAYLOAD = (
    "{what} needs the KV transfer plane, whose payload is blocks of keys and values; "
    "gated short-convolution layers (layer_kinds has 'conv') keep the last rows ahead of their filter a slot, "
    "which no block holds and nothing snapshots at a block's boundary (kv_transfer.py, ROADMAP R5)"
)


# And of a prefix that ends where the next token is drawn. Under generation by
# diffusion over blocks a prompt is cached up to its last block's edge and the
# first token comes from a block pass, not from the prompt's last chunk.
_BLOCK_POOL_KV_PAYLOAD = (
    "{what} needs the KV transfer plane, whose hand-off carries a prompt's rows and the first token drawn from its "
    "last chunk; under generation by diffusion over blocks (block_diffusion > 0) a prompt is cached to its last "
    "block's edge and tokens come from block passes (serve/llm/engine.py, ROADMAP R7)"
)


def _kv_payload_refusal(cfg) -> Optional[str]:
    """Why this configuration's pool cannot feed the KV transfer plane (None: it can)."""
    if cfg.block_diffusion:
        return _BLOCK_POOL_KV_PAYLOAD
    if cfg.latent_attention:
        return _LATENT_POOL_KV_PAYLOAD
    if "linear" in cfg.layer_kinds:
        return _STATE_POOL_KV_PAYLOAD
    if "mamba" in cfg.layer_kinds:
        return _SSM_POOL_KV_PAYLOAD
    if "conv" in cfg.layer_kinds:
        return _CONV_POOL_KV_PAYLOAD
    return _PATTERN_POOL_KV_PAYLOAD if cfg.layer_kinds else None


class _PrefixEntry:
    __slots__ = ("bid", "refs", "stamp")

    def __init__(self, bid: int, refs: int, stamp: float):
        self.bid = bid
        self.refs = refs
        self.stamp = stamp


class _Step:
    """A decode step that was dispatched and whose ids the host has not
    fetched: what the program returned, the requests it draws a token for and
    their slots (a request may leave its slot before the fetch), and what the
    iteration that emits its tokens reports of it. ``reqs`` are the ``rows``
    requests of its decode rows and then, if the step carried the LAST chunk
    of a prompt, that request: its last token's row rode in the step too, and
    the step draws its first token into its slot's id."""

    __slots__ = ("ids", "reqs", "slots", "rows", "width", "context_tokens", "window_tokens", "passes")

    def __init__(self, ids, active: list, first, width: int, context_tokens: int, window_tokens: int, passes=None):
        self.ids = ids
        self.reqs = active + [first] if first is not None else active
        self.slots = [r._sched_slot for r in self.reqs]
        self.rows = len(active)
        self.width = width
        self.context_tokens = context_tokens
        self.window_tokens = window_tokens
        # A block pass (generation by diffusion over blocks): per request (the block's start, the index of this
        # pass in the block, the positions masked before it, whether it is the commit pass).
        self.passes = passes


class LLMEngine:
    def __init__(
        self,
        params,
        cfg,
        *,
        num_slots: int = 8,
        block_size: int = 16,
        max_model_len: Optional[int] = None,
        num_blocks: Optional[int] = None,
        prefill_chunk: int = 32,
        role: str = "both",
        cluster_prefix: bool = False,
        cluster_prefix_max: int = 16,
        handoff_ttl_s: float = 120.0,
        denoising_steps: int = 0,
    ):
        """``denoising_steps``: read only where ``cfg.block_diffusion`` (the module
        docstring's paragraph): denoising passes a block (0: one a position)."""
        from ray_tpu.models.generate import (
            MOE_CHOICE,
            MOE_COUNTS,
            cache_token_bytes,
            init_moe_choice,
            init_moe_counts,
            init_paged_cache,
            kernel_reads,
            latent_kernel_reads,
            pool_reach,
            ring_blocks,
            state_kind,
            state_slot_bytes,
        )
        from ray_tpu.models.transformer import latent_softmax_scale

        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got {role!r}")
        refusal = _kv_payload_refusal(cfg)
        if refusal and (role != "both" or cluster_prefix):
            raise ValueError(
                refusal.format(what=f"role={role!r}" if role != "both" else "cluster_prefix=True")
            )
        self.cfg = cfg
        # Disaggregation role (ISSUE 20). "prefill": requests terminate at
        # prefill completion with a sealed-KV handoff descriptor instead of
        # entering decode, and the prefill queue runs shortest-remaining-
        # first (a prefill-only pool has no decode fairness to protect, so
        # SJF is safe and is what keeps short prompts from queueing behind
        # long ones — the disaggregation TTFT win). "decode" behaves like
        # "both" at the engine level (it must keep full prefill capability
        # for teacher-forced resumption and migration recompute) — the role
        # tag exists for routing/config introspection.
        self.role = role
        self.cluster_prefix = bool(cluster_prefix)
        self.cluster_prefix_max = int(cluster_prefix_max)
        self.handoff_ttl_s = float(handoff_ttl_s)
        # Published prefix entries (deepest chain hash -> sealed payload +
        # registry row keys), LRU-ordered; overflow frees the sealed copy
        # and retracts its rows. _pub_oids is the same-engine import guard.
        self._published: "OrderedDict[bytes, dict]" = OrderedDict()
        self._pub_oids: set[str] = set()
        # Outstanding handoff exports (oid -> reap deadline): the decode
        # side releases the pin after importing; the TTL reaper frees
        # payloads whose handoff never completed (proxy died mid-flight).
        self._exports: dict[str, float] = {}
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        self.max_model_len = int(max_model_len or cfg.max_seq_len)
        self.n_max = -(-self.max_model_len // self.block_size)  # blocks/seq
        # A decode step that reads its pool in place, through a kernel
        # (``generate.kernel_reads``: a latent pool, or one group of key and
        # value leaves, on a TPU), stops at each row's length whatever the
        # table's width: the whole table, one program, no ladder.
        self._block = int(cfg.block_diffusion)
        self._reads_in_place = kernel_reads(cfg, paged=True, q=self._block or 1)
        # A latent pool's prefill program asks the same predicate at the chunk's width, for ``latent_kernel_chunks``.
        self._chunk_reads_in_place = latent_kernel_reads(cfg, paged=True, q=int(prefill_chunk))
        self._latent_scale = latent_softmax_scale(cfg) if cfg.latent_attention else None
        self._view_rungs = (self.n_max,) if self._reads_in_place else _view_rungs(self.n_max)
        # Decode steps run at each width, for stats()["decode_width_steps"].
        self._width_steps = {w: 0 for w in self._view_rungs}
        # Default pool: every slot can run to max_model_len (+1 null block)
        # — preemption-free unless the caller sizes the pool down.
        self.num_blocks = int(num_blocks or self.num_slots * self.n_max + 1)
        self.prefill_chunk = int(prefill_chunk)
        if self._block:
            # Then a cached block's rows depend on that block's tokens and its predecessors' only, a chunk starts and
            # ends on a block's edge, and the chain hash of the prefix cache stays what it is.
            if self.block_size % self._block or self.prefill_chunk % self._block:
                raise ValueError(
                    f"block_diffusion = {self._block} must divide block_size = {self.block_size} and "
                    f"prefill_chunk = {self.prefill_chunk}"
                )
            self.denoising_steps = int(denoising_steps) or self._block
            if not 1 <= self.denoising_steps <= self._block:
                raise ValueError(f"denoising_steps {denoising_steps}: between 1 and the block's {self._block} positions")
        listen_for_compiles()
        listen_for_gc()
        self.spans = EngineSpans()
        import jax

        # Under a layer pattern the window layers have a group of pool leaves
        # of their own, in which every slot owns one RING of ``ring_blocks``
        # blocks for good (slot s: blocks 1 + s * ring_blocks ..): a request
        # has it whole from admission to its end, nothing of it is allocated
        # or freed, and however long the row grows a window layer holds and
        # gathers no more. The full layers keep the block tables above.
        reach = pool_reach(cfg)
        self.ring_blocks = ring_blocks(cfg.sliding_window, self.prefill_chunk, self.block_size) if "ring" in reach else 0
        self.num_window_blocks = self.num_slots * self.ring_blocks + 1 if "ring" in reach else 0
        # Layers that keep a recurrent state (linear attention, Mamba-2 blocks)
        # have a group of their own too, with no blocks at all: slot s owns row
        # s of its leaves for good (the state and the convolution's carried rows, a layer),
        # whatever its request's length. Nothing resets it on the host: the
        # first chunk of an admitted request, fresh or back from a preemption,
        # says in its row that the state starts from zero (``_chunk_inputs``),
        # and a decode row of an inactive slot moves nothing
        # (``generate._StateAccess``). So a run-ahead row that was dropped, or
        # a step's last write for a request that ended, leaves a state that
        # the slot's next tenant never reads.
        self.state_slot_bytes = state_slot_bytes(cfg)
        self._state_kind = state_kind(cfg)
        self._state_cols = _STATE_COLS if "state" in reach else 0
        self._rings = 1 + np.arange(self.num_slots * self.ring_blocks, dtype=np.int32).reshape(
            self.num_slots, self.ring_blocks
        )
        self.params = params
        with stage(self.spans.stages, "pool"):
            pool = init_paged_cache(
                cfg, self.num_blocks, self.block_size, self.num_window_blocks,
                state_slots=self.num_slots if "state" in reach else 0,
            )
            # Bytes one token holds in each group of pool leaves, all its layers,
            # and in the pool at large.
            self._group_token_bytes = cache_token_bytes(cfg)
            self.kv_token_bytes = sum(self._group_token_bytes.values())
            if cfg.routed_experts:
                # Expert counters ride the pool through both programs, donated
                # with it and updated on the device; ``stats()`` reads them.
                pool[MOE_COUNTS] = init_moe_counts(cfg)
                # And beside each cached token the experts it took (one word a
                # token a layer), for ``submit(return_routed_experts=True)``.
                pool[MOE_CHOICE] = init_moe_choice(cfg, self.num_blocks, self.block_size)
            self._cache = jax.block_until_ready(pool)
            self._fuses = self._shape_fuses()
            self._moe_wanted: Optional[threading.Event] = None
            self._moe_asking = threading.Lock()  # one asker at a time
            # The counters as last read, a NumPy array. Read once here, which
            # builds the copy's program before the replica is ready: the first
            # ``stats()`` of a serving engine compiles nothing inside a stream.
            self._moe_read = np.asarray(self._copy_moe_counts()) if cfg.routed_experts else None
            self._moe_folded = (0, 0, 0)  # assignments (held, all, identity picks) of it that ``LLM`` has
        # Block 0 is the reserved null block — never handed out.
        self._free: list[int] = list(range(self.num_blocks - 1, 0, -1))
        self._prefix: dict[bytes, _PrefixEntry] = {}
        self._bid_hash: dict[int, bytes] = {}
        # Evictable (refs-0) prefix entries in LRU order: insertion order IS
        # recency (pushed on the refs 1->0 transition, popped from the front
        # for eviction) — O(1) instead of scanning _prefix per allocation.
        self._lru: "OrderedDict[bytes, None]" = OrderedDict()
        self._slots: list[Optional[LLMRequest]] = [None] * self.num_slots
        self._waiting: deque[LLMRequest] = deque()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._crashed: Optional[str] = None  # set under _lock by the crash sweep
        self._draining = False  # drain-before-retire: refuse NEW submits only
        self._rid = itertools.count()
        self._admit_seq = itertools.count()
        # Per-engine counters for stats()/tests; the process-global LLM
        # stats object (metrics) is bumped in parallel — several engines in
        # one process fold into one exported series, like rpc.WIRE.
        self._counts = {
            "admitted": 0,
            "finished": 0,
            "cancelled": 0,
            "preemptions": 0,
            "prefix_hit_blocks": 0,
            "prefix_miss_blocks": 0,
            "evicted_blocks": 0,
            "handoffs": 0,
            "handoff_exports": 0,
            "handoff_failed": 0,
            "prefix_import_hits": 0,
            "prefix_import_misses": 0,
            "prefix_import_errors": 0,
            # Dispatches after which the pool passed in was still alive: the
            # program copied the pool instead of updating it in place. 0 is right.
            "kv_pool_not_donated": 0,
            # Rows of [., V] logits a program handed to the host instead of
            # token ids (the draw is inside the programs). 0 is right.
            "host_logit_rows": 0,
            # Decode steps dispatched; those of them dispatched while the step
            # before was still unfetched (their carried rows took its ids on
            # the device); rows whose id was dropped at its fetch because the
            # request was cancelled or preempted while the step ran.
            "decode_steps": 0,
            "decode_steps_run_ahead": 0,
            # Those of them that carried the pass's prefill chunk in their
            # program (``_shape_fuses``): one read of the weights, not two.
            "decode_steps_with_chunk": 0,
            "decode_rows_dropped": 0,
            # Those dispatched to the program that reads the latent pool
            # through its kernel (``_reads_in_place`` of a latent pool): all
            # of them or none. And the same of a pool of keys and values.
            "latent_kernel_steps": 0,
            "kv_kernel_steps": 0,
            # Prefill passes whose program reads the latent pool through the
            # chunk's kernel (``_chunk_reads_in_place``): all of them or none.
            "latent_kernel_chunks": 0,
            # Tokens the prefill chunks carried that were real, and the
            # padding behind a prompt's last ones: what the fixed chunk wastes.
            "chunk_tokens_valid": 0,
            "chunk_tokens_padded": 0,
            # Admissions that made a slot's recurrent state start from zero
            # (linear-attention layers: every admission, a re-admission too).
            "state_resets": 0,
        }
        if self._block:
            # Generation by diffusion over blocks: passes dispatched (each is a decode step too), rows of them
            # that committed a block, tokens those commits emitted, and passes fetched before the next was
            # dispatched (a request that asked for its passes one by one).
            self._counts.update(block_passes=0, block_commits=0, block_tokens_emitted=0, block_passes_synced=0)
        # The decode step in flight: dispatched, its ids not fetched.
        self._inflight: Optional[_Step] = None
        with stage(self.spans.stages, "jit_build"):
            self._decode_fn, self._prefill_fn, self._fused_fn = _compiled_fns(cfg, self.ring_blocks)
            if self._block:
                self._decode_fn = _block_pass_fn(cfg)
        _with_room(self._build_programs)
        # Prefill passes whose routed experts' matmuls run the grouped kernel whose row tile fits a group: the
        # prefill program's own predicate (``generate.experts_run``) asked at the chunk's rows, all of them or none.
        # BEHIND the build, the counter too, since PR 49: asked beside ``_chunk_reads_in_place`` with the key in the
        # literal above, a Trinity replica built its seven unchanged decode programs in 15.3 s for 12.5 (v5e). What
        # it moved was this frame's size, and with it where the build's calls met a boundary of the data stack
        # (``_with_room``, PR 52); from there the build takes 9.4 s wherever these statements stand.
        from ray_tpu.models.generate import experts_run

        self._chunk_experts_in_kernel = experts_run(cfg, self.prefill_chunk) == "kernel"
        self._counts["grouped_kernel_chunks"] = 0
        self._thread = threading.Thread(
            target=self._loop, name="llm-engine", daemon=True
        )
        # Live-engine registry: the flush-time metrics collector sums the
        # gauge-shaped state (running/waiting/KV utilization) across every
        # engine whose scheduler is still running; _loop's exit (stop OR
        # crash) withdraws this engine so the gauges never go stale.
        ENGINES.add(self)
        self._thread.start()

    # ------------------------------------------------------------------
    # public surface (any thread)
    # ------------------------------------------------------------------

    @any_thread
    def submit(
        self,
        tokens,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        resume_tokens=None,
        kv_import=None,
        request_id: str = "",
        t_recv_ns: int = 0,
        return_routed_experts: bool = False,
        return_state: bool = False,
        return_block_passes: bool = False,
    ) -> LLMRequest:
        """``return_block_passes`` (generation by diffusion over blocks): ``req.block_passes``
        gains one record a pass of every block the request generates, in order:
        ``{"start", "pass", "commit", "ids", "experts"}``: the block's first
        position, the index of the pass in its block, whether it was the commit
        pass, the block's B ids AFTER the pass (``generate.BLOCK_MASKED`` where
        still masked; a position holds a token from the pass that transferred it
        on) and, with routed experts, the experts each position took IN the pass,
        int [B, expert layers, k]. Such a request's passes are fetched one by one
        (``stats()["block_passes_synced"]``): what the benchmark's float32
        reference needs to rebuild each pass's input, and nothing traffic asks for.

        ``return_state`` (layers that keep something a slot): a request that
        completes leaves in ``req.state`` what its slot's recurrent state is
        after the last token fed to the model (prompt + generated - 1: a request
        ends by count and the last token drawn is never fed), [linear layers,
        heads, dk, dv] in the pool's dtype (gated short convolutions, which keep
        no state: the rows a slot carries ahead of each filter, [conv layers,
        conv_cache - 1, d_model]), read from the pool once, as the request
        ends: what the benchmark's check holds to the float32 recurrence, and
        what a prefix cache or a handoff of such a model would have to carry.

        ``return_routed_experts`` (routed-expert models): a request that
        completes leaves in ``req.routed_experts`` the experts each token fed
        to the model took, int [prompt + generated - 1, expert layers, k] (the
        last token drawn is never fed): what a rollout hands its trainer to
        replay the routing, read from the pool once, as the request ends.

        ``request_id`` / ``t_recv_ns``: the proxy's identifier of this
        request and its CLOCK_MONOTONIC stamp of receiving it (both ride the
        headers it forwards); they go into the request's record.

        ``resume_tokens``: tokens this request ALREADY emitted on a
        replica that died mid-stream. They are teacher-forced through
        chunked prefill exactly like recompute preemption re-admission
        (they pre-seed the generated list, so admission's target covers
        them) and are NEVER re-emitted on the token queue — the stream
        continues from position len(resume_tokens), bit-identically under
        the counter-based per-request RNG stream.

        ``kv_import``: a sealed-KV handoff descriptor from a prefill-pool
        replica. The payload is pulled HERE on the caller thread (network
        I/O must not stall the scheduler) and scattered into freshly
        allocated blocks at admission, so prefill resumes at the imported
        position instead of recomputing the prompt. Any import failure
        degrades to a plain recompute — the request still completes."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("empty prompt")
        resume = [int(t) for t in (resume_tokens or ())]
        if len(resume) > int(max_new_tokens):
            raise ValueError(
                f"resume_tokens ({len(resume)}) exceeds max_new_tokens "
                f"({max_new_tokens})"
            )
        if len(tokens) + int(max_new_tokens) > self.max_model_len:
            raise ValueError(
                f"prompt ({len(tokens)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_model_len {self.max_model_len}"
            )
        # A request whose full extent can never be backed by the pool would
        # park at the admission FIFO head forever (and starve everything
        # behind it) — reject it here, the only place that can say why.
        max_blocks = (len(tokens) + int(max_new_tokens) - 1) // self.block_size + 1
        if max_blocks > self.num_blocks - 1:
            raise ValueError(
                f"request needs up to {max_blocks} KV blocks but the pool "
                f"only has {self.num_blocks - 1}; raise num_blocks"
            )
        req = LLMRequest(
            f"llm-{next(self._rid)}", tokens, max_new_tokens, temperature, top_k, seed
        )
        req._sched_generated = resume
        if return_routed_experts and not self.cfg.routed_experts:
            raise ValueError("return_routed_experts needs a model with routed experts")
        req.return_routed_experts = bool(return_routed_experts)
        if return_state and not self.state_slot_bytes:
            raise ValueError(
                "return_state needs a model with linear-attention layers, Mamba-2 blocks or gated short convolutions "
                "(layers that keep something a slot)"
            )
        req.return_state = bool(return_state)
        if return_block_passes and not self._block:
            raise ValueError("return_block_passes needs a model generated by diffusion over blocks (block_diffusion > 0)")
        req.return_block_passes = bool(return_block_passes)
        req.request_id = str(request_id or "")
        req.t_recv = int(t_recv_ns) / 1e9 if t_recv_ns else None
        ctx = tracing.get_current_span_context()
        if ctx is not None:
            req.trace_id, req.span_id = ctx["trace_id"] or "", ctx["span_id"] or ""
        # Reuse applies to blocks fully inside tokens[:-1]: at least one
        # prompt token always runs through prefill so admission has logits
        # to sample the first output from.
        n_hashable = (len(tokens) - 1) // self.block_size
        # Under a layer pattern no block is registered and no hit taken: a hit
        # would also need the window layers' rows of the prefix's last
        # ``sliding_window`` tokens, which went with the ring that held them,
        # or the linear layers' state after the prefix's last token, which
        # nothing kept.
        if not self.cfg.layer_kinds:
            req._sched_hashes = block_hashes(tokens, self.block_size)[:n_hashable]
        if len(resume) >= int(max_new_tokens):
            # Already complete on arrival (the dead replica emitted the last
            # token but not the terminal event): nothing to generate.
            req._finished = True
            req._sched_state = "done"
            req.t_done = time.monotonic()
            req._q.put(("done", "complete"))
            self.spans.end_request(req, "finished")
            return req
        if kv_import is not None:
            refusal = _kv_payload_refusal(self.cfg)
            if refusal:
                raise ValueError(refusal.format(what="kv_import"))
            self._attach_handoff_import(req, kv_import)
        elif self.cluster_prefix and not resume and req._sched_hashes:
            self._attach_cluster_prefix(req)
        with self._lock:
            # A stopped scheduler can never serve this request — fail the
            # submit instead of parking the consumer on a queue nobody
            # feeds. Both the crash handler and the shutdown drain set
            # _crashed and sweep _waiting under this same lock, so a racing
            # submit either lands in the sweep (finished with the error) or
            # raises here. White-box tests that drive the scheduler by hand
            # after shutdown() re-open submits by clearing _crashed.
            if self._crashed is not None:
                raise RuntimeError(self._crashed)
            if self._draining:
                # TYPED: a submit racing the replica-gate/engine-drain
                # window must read as went-away to the proxy/handle (one
                # bounded reassign), not as an app bug 500.
                from ray_tpu.exceptions import ReplicaDrainingError

                raise ReplicaDrainingError(
                    msg="llm engine is draining (replica retiring); "
                    "resubmit on another replica"
                )
            self._waiting.append(req)
        self._wake.set()
        return req

    @any_thread
    def cancel(self, req: LLMRequest):
        """Client disconnect: mark the request; the scheduler frees its slot
        and KV blocks on its next iteration (sub-millisecond when active)."""
        req.cancelled.set()
        self._wake.set()

    @any_thread
    def drain(self):
        """Drain-before-retire: refuse NEW submits; everything already
        accepted (running slots + the wait queue — their clients hold live
        streams) decodes to completion. The replica retires once its
        in-flight work hits zero or drain_timeout_s expires."""
        with self._lock:
            self._draining = True

    @any_thread
    def stats(self) -> dict:
        """Best-effort snapshot (plain-int reads) for tests and benches."""
        moe = self._moe_stats()
        return {
            **({"moe": moe} if moe else {}),
            "kv_token_bytes": self.kv_token_bytes,
            "kv_groups": self._kv_groups(),
            # Rows of the residual path (hyper-connections' streams; 1: the plain residual).
            "residual_streams": self.cfg.hc_mult or 1,
            # The softmax scale latent attention runs at (``transformer.latent_softmax_scale``: YaRN's mscale in it).
            **({"latent_softmax_scale": self._latent_scale} if self.cfg.latent_attention else {}),
            "num_blocks": self.num_blocks - 1,
            "free_blocks": len(self._free),
            "cached_blocks": len(self._prefix),
            "running": sum(r is not None for r in self._slots),
            "waiting": len(self._waiting),
            "draining": self._draining,
            "role": self.role,
            "published_prefixes": len(self._published),
            "pending_exports": len(self._exports),
            **self._counts,
            "decode_width_steps": dict(self._width_steps),
            **({"block_length": self._block, "denoising_steps": self.denoising_steps}
               if self._block else {}),
            **self.spans.totals(),
        }

    def _kv_groups(self) -> dict:
        """Per group of pool leaves: its layers' bytes a token, its blocks and
        those in use. ``"full"``: the layers whose blocks grow with a row (all
        of them without a layer pattern). ``"window"``: the window layers of a
        pattern, ``ring_blocks`` a running request whatever its length.
        ``"state"``: the layers of ``kind`` (``generate.state_kind``) that keep a recurrent state or
        a filter's carried rows, ``bytes_per_slot`` for each of ``num_slots`` for good, of which
        ``slots_in_use`` hold a request's."""
        groups = {
            "full": {
                "kv_token_bytes": self._group_token_bytes["full"],
                "num_blocks": self.num_blocks - 1,
                "blocks_in_use": self.num_blocks - 1 - len(self._free),
            }
        }
        if self.state_slot_bytes:
            groups["state"] = {
                "kind": self._state_kind,
                "bytes_per_slot": self.state_slot_bytes,
                "num_slots": self.num_slots,
                "slots_in_use": sum(r is not None for r in self._slots),
            }
        if self.ring_blocks:
            groups["window"] = {
                "kv_token_bytes": self._group_token_bytes["window"],
                "num_blocks": self.num_window_blocks - 1,
                "blocks_in_use": self.ring_blocks * sum(r is not None for r in self._slots),
                "ring_blocks": self.ring_blocks,
            }
        return groups

    @any_thread
    def _moe_stats(self) -> Optional[dict]:
        """The device-side expert counters, fetched now: for ``"decode"``
        steps and ``"prefill"`` chunks apart, ``steps`` (those that routed a
        token), per expert layer ``assignments`` [E] (tokens sent to each
        expert), and the sums over those steps of ``experts_touched`` and of
        ``fullest_expert_load`` (over ``steps``: a layer's mean a step). Where
        the program holds a share of the experts (``cfg.expert_share``) all of
        these count the experts HELD, E of them, and ``assignments_all``, a
        number an expert layer, every assignment the router made, held or
        not (without a share: the sum of ``assignments``). Where the router
        has identity experts (``cfg.zero_experts``) two numbers an expert
        layer more, what the device alone knows: ``picks_identity``, the picks
        that were identities (they cost a row's width of multiplies; the picks
        of other chips' experts are ``assignments_all`` less these and the sum
        of ``assignments``), and ``rows_without_held``, the rows none of whose
        picks was held here (of ``assignments_all / experts_per_token`` rows).
        Only the scheduler
        thread may read the pool, which every dispatch donates: it is asked,
        and answers between two passes."""
        if not self.cfg.routed_experts:
            return None
        with self._moe_asking:
            if self._thread.is_alive():  # a stopped scheduler answers nothing: the last reading stands
                asked = threading.Event()
                self._moe_wanted = asked
                self._wake.set()
                asked.wait(1.0)
            read = self._moe_read
        E = self.cfg.held_experts
        return {
            kind: {
                "steps": int(of[0, E + 2]),
                "assignments": of[:, :E].tolist(),
                "assignments_all": self._moe_assignments_all(of).tolist(),
                "experts_touched": of[:, E].tolist(),
                "fullest_expert_load": of[:, E + 1].tolist(),
                **(
                    {"picks_identity": of[:, -2].tolist(), "rows_without_held": of[:, -1].tolist()}
                    if self.cfg.zero_experts  # the last two columns: ``generate._picks_apart``
                    else {}
                ),
            }
            for kind, of in zip(("decode", "prefill"), read)
        }

    def _moe_assignments_all(self, counts: np.ndarray) -> np.ndarray:
        """Every assignment the router made, a number an expert layer, from
        counters [..., expert layers, columns]: a column of its own where the
        program holds a share of the experts or routes over identity experts
        (``generate.counts_every_pick``), else the held ones' sum."""
        from ray_tpu.models.generate import counts_every_pick

        E = self.cfg.held_experts
        return counts[..., E + 3] if counts_every_pick(self.cfg) else counts[..., :E].sum(axis=-1)

    @any_thread
    def refresh_moe_counts(self):
        """Asks the scheduler to read the expert counters at its next pass and
        waits for nothing: what ``/metrics`` does at a flush, so that the next
        one finds them (``_fold_moe_read``)."""
        if self.cfg.routed_experts and self._moe_wanted is None and self._thread.is_alive():
            self._moe_wanted = threading.Event()
            self._wake.set()

    def _fold_moe_read(self):
        """Adds what the counters grew by since they were last read to the
        process's ``LLM`` stats (``ray_tpu_serve_llm_moe_assignments_total``)."""
        held = int(self._moe_read[..., : self.cfg.held_experts].sum())
        routed = int(self._moe_assignments_all(self._moe_read).sum())
        identity = int(self._moe_read[..., -2].sum()) if self.cfg.zero_experts else 0
        was_held, was_routed, was_identity = self._moe_folded
        LLM.moe_assignments_held += held - was_held
        LLM.moe_assignments_elsewhere += (routed - held - identity) - (was_routed - was_held - was_identity)
        LLM.moe_picks_identity += identity - was_identity
        self._moe_folded = (held, routed, identity)

    def _copy_moe_counts(self):
        """The expert counters, copied on the device: the host's view of the
        buffer itself (on a CPU backend it IS the buffer) would keep the next
        dispatch from donating it."""
        import jax.numpy as jnp

        from ray_tpu.models.generate import MOE_COUNTS

        return jnp.copy(self._cache[MOE_COUNTS])

    @any_thread
    def shutdown(self, timeout: float = 10.0):
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=timeout)

    def check_health(self) -> bool:
        if not self._thread.is_alive() and not self._stop.is_set():
            raise RuntimeError("llm engine scheduler thread died")
        return True

    # ------------------------------------------------------------------
    # disaggregation: KV handoff import + cluster prefix tier (ISSUE 20)
    # ------------------------------------------------------------------

    @staticmethod
    def _own_addr() -> str:
        from ray_tpu._private import worker_context

        cw = worker_context.get_core_worker_if_initialized()
        return ":".join(str(x) for x in cw.address) if cw is not None else "local"

    @blocking
    def _attach_handoff_import(self, req: LLMRequest, desc: dict):
        """Pull a prefill-pool replica's sealed KV payload on the SUBMIT
        thread and stage it for admission-time scatter. Failure is not
        fatal: the request recomputes its prompt like any fresh submit."""
        from ray_tpu.serve.llm import kv_transfer

        try:
            payload = kv_transfer.fetch_kv_payload(desc, release=True)
        except Exception as e:
            self._counts["handoff_failed"] += 1
            _flight.record(
                "llm_kv_handoff",
                f"{str(desc.get('oid', '?'))[:12]}:failed:{type(e).__name__}",
            )
            return
        req._sched_kv_import = (payload, int(desc["kv_pos"]))
        LLM.handoffs += 1
        self._counts["handoffs"] += 1
        src = ":".join(str(x) for x in desc.get("addr", ()))
        _flight.record(
            "llm_kv_handoff",
            f"{desc['oid'][:12]}:{desc.get('blocks', 0)}blk:"
            f"{desc.get('nbytes', 0)}B:{src}->{self._own_addr()}",
        )

    @blocking
    def _attach_cluster_prefix(self, req: LLMRequest):
        """Bounded longest→shortest cluster-registry probe for this
        prompt's chain hashes. A hit stages the holder's sealed KV for
        admission-time scatter (exactly the handoff import path); any
        failure falls back to recompute. At most 4 registry lookups and
        ONE payload fetch per submit — the local prefix cache stays the
        fast path and short-circuits the probe entirely."""
        from ray_tpu._private import worker_context
        from ray_tpu.exceptions import DeviceObjectLostError
        from ray_tpu.serve.llm import kv_transfer

        cw = worker_context.get_core_worker_if_initialized()
        if cw is None:
            return
        n = len(req._sched_hashes)
        depths = sorted(
            {n, n - 1, n // 2, n // 4} & set(range(1, n + 1)), reverse=True
        )[:4]
        probed = False
        for d in depths:
            h = req._sched_hashes[d - 1]
            if h in self._prefix:
                # Local cache already covers depth d — admission will take
                # the refcounted hit; an import can only do worse. (Benign
                # cross-thread dict read: a stale view just costs a probe.)
                break
            row = kv_transfer.lookup_prefix_row(cw, h)
            probed = True
            if row is None:
                continue
            if row.get("oid") in self._pub_oids:
                continue  # our own publication — importing it is recompute with extra steps
            use = min(int(row.get("use_blocks", 0)), d)
            if use < 1 or int(row.get("block_size", 0)) != self.block_size:
                continue
            desc = {
                "oid": row["oid"],
                "addr": row["addr"],
                "nbytes": int(row.get("nbytes", 0)),
                "kv_pos": use * self.block_size,
                "blocks": use,
                "block_size": self.block_size,
            }
            try:
                payload = kv_transfer.fetch_kv_payload(desc, release=False)
            except Exception as e:
                LLM.prefix_import_errors += 1
                self._counts["prefix_import_errors"] += 1
                if isinstance(e, DeviceObjectLostError):
                    # The payload died under the row (holder eviction or
                    # death): retract so the next prober skips the corpse.
                    kv_transfer.retract_prefix_rows(
                        cw, [kv_transfer.PREFIX_ROW + h.hex()], desc["oid"]
                    )
                _flight.record(
                    "llm_prefix_import",
                    f"{desc['oid'][:12]}:error:{type(e).__name__}",
                )
                return
            req._sched_kv_import = (payload[:, :, :use], use * self.block_size)
            LLM.prefix_import_hits += 1
            self._counts["prefix_import_hits"] += 1
            src = ":".join(str(x) for x in desc["addr"])
            _flight.record(
                "llm_prefix_import",
                f"{desc['oid'][:12]}:{use}blk:{desc['nbytes']}B:"
                f"{src}->{self._own_addr()}",
            )
            return
        if probed:
            LLM.prefix_import_misses += 1
            self._counts["prefix_import_misses"] += 1

    def _scatter_import(self, req: LLMRequest, cached: int):
        """Admission-time KV import (scheduler thread): write the payload
        blocks the local cache did not already cover into this request's
        freshly allocated blocks, advance prefill past the imported extent,
        and register the now-valid full prompt blocks in the LOCAL prefix
        cache (the import seeds this replica for future local hits)."""
        payload, kv_pos = req._sched_kv_import
        req._sched_kv_import = None
        # Always leave ≥1 prompt token for prefill: admission needs logits
        # to sample from, exactly the n_hashable rule.
        kv_pos = min(int(kv_pos), req._sched_target - 1)
        if kv_pos <= req._sched_pos:
            return
        imp_blocks = -(-kv_pos // self.block_size)
        if imp_blocks > len(req._sched_table) or imp_blocks > payload.shape[2]:
            return  # malformed descriptor: recompute instead of corrupting
        import jax.numpy as jnp

        idx = jnp.asarray(req._sched_table[cached:imp_blocks], jnp.int32)
        chunk = jnp.asarray(payload[:, :, cached:imp_blocks])
        dt = self._cache["k"].dtype
        self._cache["k"] = self._cache["k"].at[:, idx].set(chunk[0].astype(dt))
        self._cache["v"] = self._cache["v"].at[:, idx].set(chunk[1].astype(dt))
        req._sched_pos = kv_pos
        self._register_prefix_blocks(req)

    def _try_handoff(self, req: LLMRequest, tok: int) -> bool:
        """Prefill-role completion: seal the prompt's KV blocks as a
        transient device object, and finish the request with the ~300B
        handoff descriptor, which carries ``tok``, the first output token.
        Returns False when sealing is impossible (bare engine, seal error) —
        the caller then decodes locally, bit-identically (the counter-based
        RNG has drawn the same token at position 0 either way)."""
        from ray_tpu.serve.llm import kv_transfer

        n_exp = -(-len(req.prompt) // self.block_size)
        try:
            desc = kv_transfer.seal_kv_payload(
                self._cache,
                req._sched_table[:n_exp],
                kv_pos=len(req.prompt),
                block_size=self.block_size,
                scope="llmkv",
            )
        except Exception:
            desc = None
        if desc is None:
            return False
        req._sched_generated.append(tok)
        self._exports[desc["oid"]] = time.monotonic() + self.handoff_ttl_s
        LLM.handoff_exports += 1
        self._counts["handoff_exports"] += 1
        self._finish(req, handoff=dict(desc, tok0=tok))
        return True

    def _publish_prefix(self, req: LLMRequest):
        """Seal this request's hashable prompt prefix ONCE (an independent
        copy — pool eviction can never tear an in-flight import) and
        advertise one registry row per covered depth. LRU-capped at
        cluster_prefix_max sealed prefixes; overflow frees the payload and
        retracts its rows (read-check-delete, so a newer holder's
        last-write-wins row survives)."""
        hashes = req._sched_hashes
        if not hashes:
            return
        deep = hashes[-1]
        with self._lock:
            if deep in self._published:
                self._published.move_to_end(deep)
                return
        from ray_tpu._private import worker_context
        from ray_tpu.serve.llm import kv_transfer

        cw = worker_context.get_core_worker_if_initialized()
        if cw is None:
            return
        try:
            desc = kv_transfer.seal_kv_payload(
                self._cache,
                req._sched_table[: len(hashes)],
                kv_pos=len(hashes) * self.block_size,
                block_size=self.block_size,
                scope="llmprefix",
            )
        except Exception:
            desc = None
        if desc is None:
            return
        holder_id, _ = cw._holder_identity()
        keys = kv_transfer.publish_prefix_rows(cw, hashes, desc, holder_id)
        evicted: list[dict] = []
        with self._lock:
            self._published[deep] = {"oid": desc["oid"], "keys": keys}
            self._pub_oids.add(desc["oid"])
            while len(self._published) > self.cluster_prefix_max:
                _, entry = self._published.popitem(last=False)
                self._pub_oids.discard(entry["oid"])
                evicted.append(entry)
        for entry in evicted:
            self._retract_published(cw, entry)

    def _retract_published(self, cw, entry: dict):
        from ray_tpu.serve.llm import kv_transfer

        kv_transfer.retract_prefix_rows(cw, entry["keys"], entry["oid"])
        try:
            cw._device_manager().free(entry["oid"])
        except Exception:
            pass

    def _reap_exports(self):
        """Free handoff payloads whose descriptor never came back (proxy
        died between prefill and decode-assign) — the importing side's pin
        release is the fast path, this TTL is the backstop."""
        if not self._exports:
            return
        now = time.monotonic()
        stale = [oid for oid, dl in self._exports.items() if dl < now]
        if not stale:
            return
        from ray_tpu._private import worker_context

        cw = worker_context.get_core_worker_if_initialized()
        for oid in stale:
            self._exports.pop(oid, None)
            if cw is not None:
                try:
                    cw._device_manager().free(oid)
                except Exception:
                    pass

    def _teardown_cluster_tier(self):
        """Engine exit (shutdown or crash): retract every registry row this
        engine published and free the sealed payloads + stale exports, so
        the GCS KV returns to baseline and no importer chases a corpse."""
        from ray_tpu._private import worker_context

        cw = worker_context.get_core_worker_if_initialized()
        with self._lock:
            pubs = list(self._published.values())
            self._published.clear()
            self._pub_oids.clear()
        for entry in pubs:
            if cw is not None:
                self._retract_published(cw, entry)
        for oid in list(self._exports):
            self._exports.pop(oid, None)
            if cw is not None:
                try:
                    cw._device_manager().free(oid)
                except Exception:
                    pass

    # ------------------------------------------------------------------
    # scheduler (one dedicated thread owns everything below)
    # ------------------------------------------------------------------

    @blocking
    def _loop(self):
        """One pass: admit and sweep cancels, pick the prefilling request whose
        chunk is next, then the decode tick, which runs ONE STEP AHEAD: it
        dispatches step N+1 while step N is still on the device, and only then
        fetches and emits N's tokens. **Where the pass has a chunk AND rows
        that decode and the engine's shape fuses (``_shape_fuses``), the chunk
        rides inside step N+1**: one program, one read of the weights
        (``_launch_step``). Otherwise (no row decodes, no chunk, a shape that
        does not fuse) the chunk is a program of its own ahead of the step. So round the loop the order is dispatch N+1 (with this
        pass's chunk) -> fetch N -> emit N -> admit -> dispatch N+2 (with the
        next chunk) -> fetch N+1 ..., or, unfused, dispatch N+1 -> fetch N ->
        emit N -> admit -> prefill chunk -> dispatch N+2 ..., and the host's
        whole share of a pass hides behind a step. One loop: with nothing in
        flight (the first step, or no decoding row) the tick dispatches from
        the host's tokens and goes on as above.

        A prompt's LAST chunk that rides a step makes its request one of the
        step's (``_Step.reqs``): the first token is drawn in the program,
        lands with the step's ids a pass later (no ``llm.prefill.fetch``), and
        the step after already feeds it from the device, as it does every
        carried row's. A chunk alone still fetches its own id, and a
        ``role == "prefill"`` engine, which never decodes, never fuses: the
        hand-off needs the first token on the host.

        Device order is what makes that safe. The pool is donated from
        program to program, so programs run in the order they were dispatched:
        a prefill chunk dispatched after step N+1 sees its writes, and a chunk
        inside a step writes blocks no decode row of that step names. Prefix
        blocks are registered, and a prefix published, when their chunk is
        DISPATCHED, alone or in a step: whoever reads them is dispatched
        later. Blocks that a cancel, a finish or a preemption releases while
        a step is in flight may be written once more by that step (a row of
        it, or its chunk, still names them); their next owner reads only
        positions it wrote itself, later in device order, and neither a decode
        row nor a chunk writes a block the prefix cache shares. A request
        cancelled or preempted while its last chunk is in flight has left its
        slot when the step lands, and the id is dropped like a decode row's
        (``decode_rows_dropped``).
        Whatever reads the pool from the host (``_routed_experts``,
        ``_publish_prefix``, ``_try_handoff``, ``_scatter_import``, the expert
        counters) reads ``self._cache``, the result of the step in flight:
        the array it must read. Leaving the loop (shutdown, crash) drops the
        step in flight unfetched; its requests end with the loop's error."""
        try:
            spans = self.spans
            while not self._stop.is_set():
                it = spans.begin(
                    len(self._waiting), sum(r is not None for r in self._slots)
                )
                asked, self._moe_wanted = self._moe_wanted, None
                try:
                    with spans.span("llm.admit") as sp:
                        self._sweep_cancelled()
                        self._reap_exports()
                        sp.set(admitted=self._admit(), waiting=len(self._waiting))
                    # If ``_moe_stats`` asked: copied before the pass dispatches,
                    # behind the step in flight and ahead of the next, so the
                    # reading below waits for no step but the one just fetched.
                    counts = self._copy_moe_counts() if asked is not None else None
                    busy = self._ticks()
                finally:
                    spans.end(it)
                if asked is not None:
                    self._moe_read = np.asarray(counts)
                    self._fold_moe_read()
                    asked.set()
                if not busy:
                    if any(r is not None for r in self._slots) or self._waiting:
                        self._wake.wait(0.02)
                    else:
                        # Fully idle: every state transition that could make
                        # work (submit/cancel/shutdown) sets _wake, so park
                        # until one does instead of spinning 50x/s.
                        self._wake.wait()
                    self._wake.clear()
        except BaseException as e:  # noqa: BLE001 — fail every consumer loudly
            msg = f"llm engine scheduler died: {type(e).__name__}: {e}"
            with self._lock:
                self._crashed = msg
                pending = list(self._slots) + list(self._waiting)
            for req in pending:
                if req is not None:
                    self._finish(req, error=msg)
            raise
        finally:
            ENGINES.discard(self)
            self._inflight = None
            with self._lock:
                if self._crashed is None:
                    self._crashed = "llm engine is shut down"
                pending = list(self._slots) + list(self._waiting)
            for req in pending:
                if req is not None:
                    self._finish(req, error=SHUTDOWN_ERROR)
            self._teardown_cluster_tier()

    def _ticks(self) -> bool:
        """A pass's programs: the chunk inside the decode step where the
        engine's shape fuses and a row decodes, else the chunk, then the step."""
        chunk = self._next_prefill()
        if self._block:
            busy = self._prefill_tick(chunk)
            return self._block_tick() or busy
        if chunk is not None and self._fuses and self._decoding(self._inflight):
            return self._decode_tick(chunk)
        busy = self._prefill_tick(chunk)
        return self._decode_tick() or busy

    def _sweep_cancelled(self):
        for req in self._slots:
            if req is not None and req.cancelled.is_set():
                self._finish(req, cancelled=True)
        with self._lock:
            stale = [r for r in self._waiting if r.cancelled.is_set()]
            for r in stale:
                self._waiting.remove(r)
        for r in stale:
            self._finish(r, cancelled=True)

    # --- block pool ---

    def _alloc_block(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        while self._lru:
            victim_hash, _ = self._lru.popitem(last=False)  # oldest refs-0
            victim = self._prefix.get(victim_hash)
            if victim is None or victim.refs > 0:
                continue  # stale LRU entry (white-box tests may desync)
            del self._prefix[victim_hash]
            self._bid_hash.pop(victim.bid, None)
            LLM.evicted_blocks += 1
            self._counts["evicted_blocks"] += 1
            _flight.record("llm_evict", f"bid={victim.bid}")
            return victim.bid
        return None

    def _release_blocks(self, req: LLMRequest):
        now = time.monotonic()
        shared = req._sched_cached_bids | req._sched_registered_bids
        for bid in req._sched_table:
            if bid in shared:
                h = self._bid_hash.get(bid)
                e = self._prefix.get(h) if h is not None else None
                if e is not None:
                    e.refs -= 1
                    e.stamp = now
                    if e.refs <= 0:  # now evictable: most-recent LRU slot
                        self._lru.pop(h, None)
                        self._lru[h] = None
                else:  # registration raced an eviction; treat as private
                    self._free.append(bid)
            else:
                self._free.append(bid)
        req._sched_table = []
        req._sched_cached_bids = set()
        req._sched_registered_bids = set()

    # --- admission ---

    def _admit(self) -> int:
        """Returns how many requests it admitted."""
        admitted = 0
        while True:
            try:
                slot = self._slots.index(None)
            except ValueError:
                return admitted
            with self._lock:
                if not self._waiting:
                    return admitted
                req = self._waiting[0]
            # Teacher-forced target: original prompt plus anything already
            # emitted before a preemption.
            target = len(req.prompt) + len(req._sched_generated)
            cached = 0
            for h in req._sched_hashes:
                e = self._prefix.get(h)
                if e is None:
                    break
                cached += 1
            need = (target - 1) // self.block_size + 1 - cached
            # Evictable supply must EXCLUDE the refs-0 entries this request
            # is about to take as cached hits — counting them double lets
            # admission proceed into an alloc loop with no blocks left.
            hit_hashes = set(req._sched_hashes[:cached])
            evictable = len(self._lru) - sum(
                1 for h in hit_hashes if h in self._lru
            )
            if len(self._free) + evictable < need:
                return admitted  # head-of-line waits for blocks (FIFO fairness)
            with self._lock:
                self._waiting.popleft()
            table: list[int] = []
            now = time.monotonic()
            for h in req._sched_hashes[:cached]:
                e = self._prefix[h]
                e.refs += 1
                e.stamp = now
                if e.refs == 1:  # left the evictable set
                    self._lru.pop(h, None)
                table.append(e.bid)
                req._sched_cached_bids.add(e.bid)
            for _ in range(need):
                bid = self._alloc_block()
                assert bid is not None  # guarded by the availability check
                table.append(bid)
            LLM.prefix_hit_blocks += cached
            self._counts["prefix_hit_blocks"] += cached
            LLM.prefix_miss_blocks += len(req._sched_hashes) - cached
            self._counts["prefix_miss_blocks"] += len(req._sched_hashes) - cached
            if cached:
                _flight.record("llm_prefix_hit", f"{req.id}:{cached}blk")
            req._sched_table = table
            req._sched_pos = cached * self.block_size
            # Under generation by diffusion over blocks the chunks fill up to the last block's edge; what lies
            # behind it (of a fresh request: the prompt's last ``n mod B`` tokens) starts the row's first block.
            req._sched_target = target - target % self._block if self._block else target
            if req._sched_kv_import is not None:
                self._scatter_import(req, cached)
            if req.t_admit is None:
                req.t_admit = now
                req.cached_tokens = req._sched_pos
            admitted += 1
            req._sched_state = "prefill"
            if self._block and req._sched_pos >= req._sched_target:  # nothing to prefill: a prompt inside one block, or all of it cached
                self._block_begin(req)
            req._sched_slot = slot
            req._sched_admit_seq = next(self._admit_seq)
            self._slots[slot] = req
            LLM.admitted += 1
            self._counts["admitted"] += 1
            if self.state_slot_bytes:  # its first chunk starts at position 0: ``_chunk_inputs`` says so to the program
                LLM.state_resets += 1
                self._counts["state_resets"] += 1
            _flight.record(
                "llm_admit",
                f"{req.id}:T{len(req.prompt)}:hit{cached}:slot{slot}",
            )

    # --- prefill (one fixed-shape chunk per tick, interleaved with decode) ---

    def _next_prefill(self) -> Optional[LLMRequest]:
        """The prefilling request whose chunk this pass runs, if any."""
        if self.role == "prefill":
            # Prefill-only pool: shortest-remaining-first. There is no
            # decode fairness to protect here, so a short prompt jumps the
            # queue instead of waiting out a long one's chunks — the
            # disaggregation TTFT win for short streams under mixed load.
            # admit_seq tiebreaks for determinism; starvation is bounded by
            # the pool being prefill-only (every job leaves at completion).
            key = lambda r: (r._sched_target - r._sched_pos, r._sched_admit_seq)  # noqa: E731
        else:
            key = lambda r: r._sched_admit_seq  # noqa: E731
        return min(
            (r for r in self._slots if r is not None and r._sched_state == "prefill"),
            key=key,
            default=None,
        )

    def _chunk_inputs(self, req: LLMRequest):
        """(tokens [1, q], rows [1, 7 + n_max]) of ``req``'s next chunk as
        NumPy arrays, and how many of the tokens are real."""
        q = self.prefill_chunk
        pos0 = req._sched_pos
        seq = req.prompt + req._sched_generated
        piece = seq[pos0 : pos0 + q]
        # NumPy first: `jnp.asarray` of a list is a program of its own
        # (a convert_element_type dispatch), of an int32 array a copy.
        fed = np.zeros((1, q), np.int32)
        fed[0, : len(piece)] = piece
        # The program draws from the row of the prompt's LAST real token
        # within this chunk: only meaningful (and only fetched) on the
        # final chunk.
        rows = self._program_rows(1, self.n_max)
        self._fill_row(rows[0], req, req._sched_target, pos0)
        if self._state_cols:
            # A chunk that starts a sequence starts its slot's state: admission
            # put the request at position 0 (no prefix hit under a pattern),
            # the first time and after a preemption.
            rows[0, _ROW_TABLE + self.ring_blocks : _ROW_TABLE + self.ring_blocks + _STATE_COLS] = (
                req._sched_slot, pos0 == 0,
            )
        LLM.chunk_tokens_valid += len(piece)
        LLM.chunk_tokens_padded += q - len(piece)
        self._counts["chunk_tokens_valid"] += len(piece)
        self._counts["chunk_tokens_padded"] += q - len(piece)
        return fed, rows, len(piece)

    def _chunk_end(self, req: LLMRequest) -> int:
        """The position ``req``'s next chunk fills up to: its target if the
        chunk is the prompt's last."""
        return min(req._sched_pos + self.prefill_chunk, req._sched_target)

    def _chunk_dispatched(self, req: LLMRequest) -> bool:
        """``req``'s next chunk is on the device: move on, register what it
        wrote. True if it was the prompt's last."""
        req._sched_pos = self._chunk_end(req)
        self._register_prefix_blocks(req)
        if req._sched_pos < req._sched_target:
            return False
        # Publish BEFORE any terminal transition: sealing gathers from
        # the request's still-allocated block table.
        if self.cluster_prefix:
            self._publish_prefix(req)
        return True

    def _prefill_tick(self, req: Optional[LLMRequest] = None) -> bool:
        """One chunk of ``req`` (default: ``_next_prefill``'s) as a program of
        its own; False if nothing prefills."""
        req = req or self._next_prefill()
        if req is None:
            return False
        import jax.numpy as jnp

        spans = self.spans
        with spans.span("llm.prefill.build", rid=req.id, pos=req._sched_pos):
            fed, rows, n = self._chunk_inputs(req)
            inputs = (jnp.asarray(fed), jnp.asarray(rows))
        spans.carried(prefill_tokens=n, chunk_context_tokens=req._sched_pos)
        self._counts["latent_kernel_chunks"] += self._chunk_reads_in_place
        self._counts["grouped_kernel_chunks"] += self._chunk_experts_in_kernel
        with spans.span("llm.prefill.dispatch", rid=req.id):
            drawn = self._run_donated(self._prefill_fn, *inputs)
        if self._block:
            # No token comes from a prompt's chunk (a position predicts itself: the first comes from a block pass), so nothing is fetched.
            if self._chunk_dispatched(req):
                self._block_begin(req)
        elif self._chunk_dispatched(req):
            with spans.span("llm.prefill.fetch", rid=req.id):
                # Waits for the step in flight too: it runs ahead of the chunk.
                drawn = np.asarray(drawn)
            # The first token is drawn before the handoff is tried: the draw
            # is keyed by (seed, position), the same token either way.
            tok = self._drawn_tokens([req], drawn, [0])[0]
            with spans.span("llm.emit", tokens=1, rid=req.id) as sp:
                if not (self.role == "prefill" and self._try_handoff(req, tok)):
                    self._emit_token(req, tok, sp.t0)
                sp.set(finished=int(req._finished))
        return True

    def _program_rows(self, n: int, width: int, block: int = 0) -> np.ndarray:
        """``n`` all-zero rows of a program's int32 input, their block table
        ``width`` blocks wide (behind the ring, under a layer pattern; behind
        the ``block`` ids of a block pass's row, ``_BLOCK_CARRIED``): an
        inactive slot (token 0 at position 0 of the null block, drawn greedily)."""
        return np.zeros((n, _ROW_TABLE + self.ring_blocks + self._state_cols + block + width), np.int32)

    def _fill_row(self, row: np.ndarray, req: LLMRequest, first: int, pos: int, ahead: int = 0):
        """``first``: column 0, the token fed (decode) or valid_to (prefill);
        ``ahead``: 1 if a step in flight draws a token of ``req`` before this
        dispatch draws its own."""
        row[_ROW_TOKEN] = first
        row[_ROW_POS] = pos
        row[_ROW_DRAW] = req._sched_draw
        row[_ROW_COUNTER] = len(req._sched_generated) + ahead
        rings = _ROW_TABLE + self.ring_blocks
        row[_ROW_TABLE:rings] = self._rings[req._sched_slot]
        table = rings + self._state_cols
        row[table : table + len(req._sched_table)] = req._sched_table

    def _run_donated(self, fn, tokens, *rest):
        """Dispatch one pool-updating program. The pool is donated: the
        arrays passed in are deleted and ``self._cache`` is rebound to the
        result in the same statement, so nothing reads a donated buffer. A
        program that raises after donating leaves no pool; the exception
        ends ``_loop``, which marks the engine crashed."""
        pool = self._cache
        drawn, self._cache = fn(self.params, tokens, pool, *rest)
        if not all(leaf.is_deleted() for leaf in pool.values()):
            self._counts["kv_pool_not_donated"] += 1
        if drawn.ndim != (2 if self._block and fn is self._decode_fn else 1):  # a block pass hands back [num_slots, B] ids
            # The draw belongs inside the program: one that hands back
            # ``[rows, V]`` logits is refused, there being nothing left on the
            # host to draw from them (nor a token column for the next step).
            self._counts["host_logit_rows"] += drawn.shape[0]
            raise TypeError(
                f"program returned {drawn.dtype}{list(drawn.shape)}, not one token id a row"
            )
        return drawn

    def _shape_fuses(self) -> bool:
        """Whether a pass with a chunk AND decode rows runs as one program
        (``_compiled_fns``' ``decode_with_chunk``), by what the engine can see
        of itself: its rows and the chunk's fill no more than one tile of the
        matrix unit, the pool is one group of key and value leaves and the
        model routes no experts (``generate.paged_decode_step_with_chunk``
        carries nothing else), and the engine decodes at all. ONE such
        program, at the whole table: a second is ~1 s of EVERY warm start, its
        trace and lowering, which no thread hides (the interpreter is one:
        PERF.md, PR 40)."""
        from ray_tpu.models.generate import one_kv_group

        return (
            self.role != "prefill"
            and self.num_slots + self.prefill_chunk <= _MXU_TILE_ROWS
            and one_kv_group(self.cfg)
            and not self.cfg.routed_experts
            and not self.cfg.block_diffusion
        )

    def _build_programs(self):
        """Build the decode program at every rung before the scheduler starts
        and, for an engine whose shape fuses (``_shape_fuses``), the step with a
        chunk and the prefill program: one dispatch of
        all-inactive rows each, which writes row 0 of the null block and
        nothing else. A width first met while serving would compile for
        seconds inside a stream; an engine that adds programs to its start
        pays for them where it can (built here the prefill program costs a
        warm Mistral-16 replica 1.0 s where its first request built it in
        1.6), and one that adds none keeps the start it had, its prefill
        program built by its first request: a cold five-layer expert replica
        has 17 s of the 90 Serve gives it to spare (PERF.md, PR 35 and 40).
        One stage record a program and stage (``stats.stage``: ``trace``,
        ``lower``, ``compile`` on the pool thread that ran it, ``first_run``),
        from which ``fused_build_s``, the trace and lowering of the step with a
        chunk, and ``decode_build_s``, all the rest of it, are computed
        (``stats.setup_seconds``); and the threads that burned most meanwhile."""
        from concurrent.futures import ThreadPoolExecutor

        import jax
        import jax.numpy as jnp

        stages, threads0 = self.spans.stages, thread_cpu_ns()
        ids = jnp.zeros((self.num_slots, self._block) if self._block else (self.num_slots,), jnp.int32)
        chunk = jnp.zeros((1, self.prefill_chunk), jnp.int32)
        # Traced, lowered and compiled apart from the call, and all three
        # held until it returns: the call then finds the trace and the
        # lowering in jit's caches, which keep them only while these
        # objects live. In a v5e replica that is 0.6 s a rung where the
        # call alone takes 0.94 (PERF.md, PR 31). Traced and lowered one
        # after another: that is Python, and threads only take the
        # interpreter from one another (from the parameter draw too, which
        # is host work as well: PERF.md, PR 40). Compiled side by side (the
        # compiler runs outside the GIL): cold, a rung of a five-layer expert
        # model takes it 8 s, seven of them in a row more than Serve gives a
        # replica to become ready (PERF.md, PR 35).
        calls = [
            (f"decode@{w}", self._decode_fn, jnp.asarray(self._program_rows(self.num_slots, w, self._block)), ids)
            for w in self._view_rungs
        ]
        if self._fuses:
            whole = [jnp.asarray(self._program_rows(n, self.n_max)) for n in (1, self.num_slots)]
            calls += [
                ("prefill", self._prefill_fn, chunk, whole[0]),
                ("decode_with_chunk", self._fused_fn, whole[1], ids, chunk, whole[0]),
            ]
        lowered = []
        for name, fn, *args in calls:
            with stage(stages, "trace", name):
                traced = fn.trace(self.params, args[0], self._cache, *args[1:])
            with stage(stages, "lower", name):
                lowered.append((name, traced, traced.lower()))

        def compile_(program):
            with stage(stages, "compile", program[0]):
                return program[2].compile()

        with ThreadPoolExecutor(max_workers=len(calls), thread_name_prefix="llm-compile") as pool:
            held = lowered, list(pool.map(compile_, lowered))
        for name, fn, *args in calls:
            with stage(stages, "first_run", name):
                drawn = jax.block_until_ready(self._run_donated(fn, *args))
            ids = drawn if fn is not self._prefill_fn else ids
        del held
        # What a step with no step before it is given as ``ids`` (none of its
        # rows reads them): a program's own output, like every other step's.
        self._no_ids = self._block_carry = ids
        self.spans.build_threads = busiest_threads(threads0, thread_cpu_ns())

    def _register_prefix_blocks(self, req: LLMRequest):
        """Publish freshly-WRITTEN full prompt blocks for reuse. Done as
        prefill progresses (never at admission): a block becomes visible to
        other admissions only once its rows exist."""
        now = time.monotonic()
        done_blocks = req._sched_pos // self.block_size
        for i, h in enumerate(req._sched_hashes[:done_blocks]):
            bid = req._sched_table[i]
            if bid in req._sched_cached_bids or bid in req._sched_registered_bids:
                continue
            if h in self._prefix:
                continue  # another sequence published this hash first
            self._prefix[h] = _PrefixEntry(bid, refs=1, stamp=now)
            self._bid_hash[bid] = h
            req._sched_registered_bids.add(bid)

    # --- decode ---

    def _decode_tick(self, chunk: Optional[LLMRequest] = None) -> bool:
        """Dispatch the step after the one in flight, then fetch and emit the
        one in flight. With none in flight, dispatch one first. ``chunk``: the
        request whose next chunk rides inside the first step dispatched."""
        step, self._inflight = self._inflight, None
        busy = step is not None or chunk is not None or bool(self._decoding(None))
        if step is None:
            step, chunk = self._launch_step(None, chunk), None
        if step is not None:
            self._inflight = self._launch_step(step, chunk)
            self._land_step(step)
        return busy

    def _decoding(self, ahead_of: Optional[_Step]) -> list:
        """The requests a step dispatched now would carry a decode row for.
        ``ahead_of`` is the step in flight, if any: a request of it is one
        token further on than the host has seen, and has no row if that token
        is its last: a request ends by count."""
        if self._block:  # counted as dispatched, whatever is in flight (``_launch_block``)
            return [
                r for r in self._slots
                if r is not None and r._sched_state == "decode" and r._sched_emit_ahead < r.max_new_tokens
            ]
        riding = ahead_of.reqs if ahead_of is not None else ()
        return [
            r
            for r in self._slots
            if r is not None
            and r._sched_state == "decode"
            and len(r._sched_generated) + (r in riding) < r.max_new_tokens
        ]

    def _back_writes(self, active: list, writes_at):
        """Every active sequence needs the last position it writes, ``writes_at(req)``,
        backed by a physical block before the step; exhaustion preempts the
        youngest (whatever a victim has in flight is dropped at its fetch)."""
        bs = self.block_size
        for req in active:
            if req._sched_slot is None or self._slots[req._sched_slot] is not req:
                continue  # preempted by an earlier needy sequence this tick
            while writes_at(req) // bs >= len(req._sched_table):
                bid = self._alloc_block()
                if bid is not None:
                    req._sched_table.append(bid)
                    continue
                # Youngest-victim policy over ALL running sequences — the
                # needy one included: when req itself is the youngest it is
                # the one preempted (minimal recompute), not an older
                # sequence carrying more progress.
                running = [r for r in self._slots if r is not None]
                victim = max(running, key=lambda r: r._sched_admit_seq)
                if victim is req:
                    if len(running) == 1:
                        # Nobody else holds blocks: preempting req would just
                        # readmit it into the same dry pool forever.
                        self._finish(
                            req,
                            error=(
                                "KV block pool exhausted with a single "
                                "running sequence; raise num_blocks"
                            ),
                        )
                    else:
                        self._preempt(req)
                    break  # req left its slot; its alloc loop is moot
                self._preempt(victim)

    def _launch_step(self, ahead_of: Optional[_Step], chunk: Optional[LLMRequest] = None) -> Optional[_Step]:
        """Build and dispatch one decode step over every row that decodes;
        None if there is none. ``ahead_of`` is the step in flight, if any: a
        request it draws a token for (``riding``) is one token and one
        position further on than the host has seen, feeds the id that step
        draws for its slot (``_ID_IN_FLIGHT``), and has no row here if that id
        is its last. Every other row (fresh from a chunk that ran alone, back
        from a preemption) feeds its last token from the host, so a slot that
        changed hands never reads the tenant before's id.

        ``chunk``: the request whose next chunk goes into this step's program
        (``decode_with_chunk``, at the whole table), if it still prefills once the rows have their blocks (a
        preemption for them may have taken it). With no row to decode nothing
        is dispatched and the chunk waits for the next pass."""
        riding = ahead_of.reqs if ahead_of is not None else ()
        bs = self.block_size

        def writes_at(r):
            return r._sched_pos + (r in riding)

        active = self._decoding(ahead_of)
        if not active:
            return None
        spans = self.spans
        with spans.span("llm.decode.build") as sp:
            self._back_writes(active, writes_at)
            # Re-derive the step batch: preemption/failure above may have
            # removed sequences from their slots.
            active = [r for r in self._decoding(ahead_of) if writes_at(r) // bs < len(r._sched_table)]
            sp.set(rows=len(active))
            if not active:
                return None
            if chunk is not None and chunk._sched_state != "prefill":
                chunk = None
            import jax.numpy as jnp

            # The step gathers and attends over the table it is handed: the
            # smallest rung that covers the longest of ITS rows' tables, as
            # the host has just extended them; with a chunk, the whole table.
            longest = max(len(r._sched_table) for r in active)
            width = self.n_max if chunk is not None else next(w for w in self._view_rungs if w >= longest)
            rows = self._program_rows(self.num_slots, width)
            context_tokens = window_tokens = 0
            window = self.cfg.sliding_window or self.max_model_len
            for req in active:
                ahead = req in riding
                self._fill_row(
                    rows[req._sched_slot],
                    req,
                    _ID_IN_FLIGHT if ahead else req._sched_generated[-1],
                    writes_at(req),
                    ahead,
                )
                context_tokens += writes_at(req) + 1
                window_tokens += min(writes_at(req) + 1, window)
            # A prompt's LAST chunk makes its request one of the step's: the
            # id drawn from the chunk comes back in the request's own slot.
            first = chunk if chunk is not None and self._chunk_end(chunk) == chunk._sched_target else None
            if first is not None:
                rows[first._sched_slot, _ROW_TOKEN] = _ID_FROM_CHUNK
            inputs = [jnp.asarray(rows), ahead_of.ids if ahead_of is not None else self._no_ids]
        if chunk is not None:
            with spans.span("llm.prefill.build", rid=chunk.id, pos=chunk._sched_pos):
                fed, chunk_rows, n = self._chunk_inputs(chunk)
                inputs += [jnp.asarray(fed), jnp.asarray(chunk_rows)]
            spans.carried(prefill_tokens=n, chunk_tokens=n, chunk_context_tokens=chunk._sched_pos)
            self._counts["decode_steps_with_chunk"] += 1
        self._width_steps[width] += 1
        self._counts["decode_steps"] += 1
        self._counts["decode_steps_run_ahead"] += ahead_of is not None
        self._counts["latent_kernel_steps" if self.cfg.latent_attention else "kv_kernel_steps"] += self._reads_in_place
        with spans.span("llm.decode.dispatch"):
            ids = self._run_donated(self._decode_fn if chunk is None else self._fused_fn, *inputs)
        if chunk is not None and self._chunk_dispatched(chunk):
            # Its last token's row was in this step, which draws its first
            # token: from here on it is a request of the step like the rows'.
            chunk._sched_pos -= 1
            chunk._sched_state = "decode"
        return _Step(ids, active, first, width, context_tokens, window_tokens)

    def _land_step(self, step: _Step):
        """Fetch a dispatched step's ids and emit them, each to the request
        that still holds the slot its row was built for: a request cancelled
        or preempted since the dispatch has left it, and its id is dropped."""
        spans = self.spans
        spans.carried(
            rows=step.rows, view_blocks=step.width, context_tokens=step.context_tokens,
            window_tokens=step.window_tokens,
        )
        with spans.span("llm.decode.fetch"):
            # Waits for this step (the next is already queued behind it, and
            # behind this pass's prefill chunk), then copies [num_slots] ids.
            ids = np.asarray(step.ids)
        toks = self._drawn_tokens(step.reqs, ids, step.slots)
        live = [
            (req, tok)
            for req, slot, tok in zip(step.reqs, step.slots, toks)
            if self._slots[slot] is req
        ]
        self._counts["decode_rows_dropped"] += len(toks) - len(live)
        with spans.span("llm.emit", tokens=len(live)) as sp:
            for req, tok in live:
                req._sched_pos += 1
                self._emit_token(req, tok, sp.t0)
            sp.set(finished=sum(req._finished for req, _ in live))

    # --- generation by diffusion over blocks (``cfg.block_diffusion``; the module docstring's paragraph) ---

    def _block_begin(self, req: LLMRequest):
        """``req`` is cached up to a block's edge, ``_sched_target`` (a fresh
        prompt, a re-admission's teacher-forced tokens): from here on it is a
        row of the block passes, its first block what lies behind the edge (a
        prompt's last ``n mod B`` tokens) and ``MASK``, its passes counted from 0:
        the same noise as the first time."""
        req._sched_state = "decode"
        req._sched_pos = req._sched_bstart = req._sched_target
        req._sched_bpass = 0
        req._sched_bmasks = self._block - (len(req.prompt) + len(req._sched_generated) - req._sched_target)
        req._sched_emit_ahead = len(req._sched_generated)
        req.block_passes = [rec for rec in req.block_passes if rec["start"] < req._sched_target]

    def _block_synced(self) -> bool:
        """Whether a pass is fetched before the next is dispatched: a request
        in a slot asked for its passes one by one."""
        return any(r is not None and r.return_block_passes for r in self._slots)

    def _block_tick(self) -> bool:
        """``_decode_tick`` of block passes: one pass ahead, or, where
        ``_block_synced``, each pass landed before the next is built."""
        step, self._inflight = self._inflight, None
        busy = step is not None or bool(self._decoding(None))
        if step is None:
            step = self._launch_block(False)
        if step is not None:
            if self._block_synced():
                self._counts["block_passes_synced"] += 1
            else:
                self._inflight = self._launch_block(True)
            self._land_block(step)
        return busy

    def _transfers(self, req: LLMRequest) -> int:
        """Positions ``req``'s next pass transfers: ``B // S``, one more in the first ``B mod S`` passes, no more
        than are masked; 0 is the commit pass."""
        S = self.denoising_steps
        return min(self._block // S + (req._sched_bpass < self._block % S), req._sched_bmasks)

    def _launch_block(self, ahead: bool) -> Optional[_Step]:
        """Build and dispatch one pass over the block of every row that
        decodes; None if there is none. ``ahead``: the pass before is still
        unfetched. Everything a row is told is known by count (``_sched_b*``,
        as dispatched): its block's start, which pass of the block this is
        (the first takes the block from the host, the prompt's tail and
        ``MASK``; later ones from the pass before on the device) and how many
        positions it transfers. A commit pass moves the row on to its next block
        here, at dispatch; its tokens are emitted when it lands."""
        B, bs = self._block, self.block_size
        active = self._decoding(None)
        if not active:
            return None
        spans = self.spans
        with spans.span("llm.decode.build") as sp:
            self._back_writes(active, lambda r: r._sched_bstart + B - 1)
            active = [r for r in self._decoding(None) if (r._sched_bstart + B - 1) // bs < len(r._sched_table)]
            sp.set(rows=len(active))
            if not active:
                return None
            import jax.numpy as jnp

            from ray_tpu.models.generate import BLOCK_MASKED

            longest = max(len(r._sched_table) for r in active)
            width = next(w for w in self._view_rungs if w >= longest)
            rows = self._program_rows(self.num_slots, width, B)
            context_tokens, passes = 0, []
            for req in active:
                row, start, n = rows[req._sched_slot], req._sched_bstart, len(req.prompt)
                commit = req._sched_bmasks == 0
                row[_ROW_TOKEN] = moved = self._transfers(req)
                row[_ROW_POS] = start
                row[_ROW_DRAW] = req._sched_draw
                row[_ROW_COUNTER] = (start - n) * B + req._sched_bpass
                if req._sched_bpass == 0:  # a block's first pass: what is known of it, from the host
                    known = (req.prompt + req._sched_generated)[start : start + B]
                    row[_ROW_TABLE : _ROW_TABLE + B] = known + [BLOCK_MASKED] * (B - len(known))
                else:
                    row[_ROW_TABLE : _ROW_TABLE + B] = _BLOCK_CARRIED
                row[_ROW_TABLE + B : _ROW_TABLE + B + len(req._sched_table)] = req._sched_table
                context_tokens += start + B
                passes.append((start, req._sched_bpass, moved, commit))
                if commit:
                    req._sched_emit_ahead = min(req.max_new_tokens, start + B - n)
                    req._sched_bstart, req._sched_bpass, req._sched_bmasks = start + B, 0, B
                else:
                    req._sched_bmasks -= moved
                    req._sched_bpass += 1
            inputs = [jnp.asarray(rows), self._block_carry]
        self._width_steps[width] += 1
        self._counts["decode_steps"] += 1
        self._counts["block_passes"] += 1
        self._counts["decode_steps_run_ahead"] += ahead
        with spans.span("llm.decode.dispatch"):
            self._block_carry = self._run_donated(self._decode_fn, *inputs)
        return _Step(self._block_carry, active, None, width, context_tokens, context_tokens, passes)

    def _land_block(self, step: _Step):
        """Fetch a dispatched pass's blocks; of each row that still holds its
        slot: a commit pass's tokens, those of the block that the request has
        not emitted and still wants, go out in order under one ``llm.emit``
        stamp and the row's position moves on by the block."""
        B, spans = self._block, self.spans
        with spans.span("llm.decode.fetch"):
            blocks = np.asarray(step.ids)  # [num_slots, B]
        # The draw and the transfer ran inside the program; the host's share is the blocks as Python ints.
        with spans.span("llm.sample", rows=len(step.reqs), sampled=sum(r.temperature > 0.0 for r in step.reqs), top_k=0):
            live = [
                (req, blocks[slot].tolist(), at)
                for req, slot, at in zip(step.reqs, step.slots, step.passes)
                if self._slots[slot] is req
            ]
        self._counts["decode_rows_dropped"] += len(step.reqs) - len(live)
        commits = sum(commit for _, _, (_, _, _, commit) in live)
        unmasked = sum(moved for _, _, (_, _, moved, _) in live)
        spans.carried(
            rows=step.rows, view_blocks=step.width, context_tokens=step.context_tokens,
            window_tokens=step.window_tokens, block_commits=commits, tokens_unmasked=unmasked,
        )
        self._counts["block_commits"] += commits
        emitted = 0
        with spans.span("llm.emit", tokens=0) as sp:
            for req, block, (start, nth, _, commit) in live:
                if req.return_block_passes:
                    rec = {"start": start, "pass": nth, "commit": commit, "ids": block}
                    if self.cfg.routed_experts:
                        rec["experts"] = self._block_experts(req, start)
                    req.block_passes.append(rec)
                if not commit:
                    continue
                req._sched_pos = start + B
                first = start - len(req.prompt)  # the index among the request's tokens of the block's first position
                for j, tok in enumerate(block):
                    if len(req._sched_generated) <= first + j < req.max_new_tokens:
                        emitted += 1
                        self._emit_token(req, tok, sp.t0)
            sp.set(tokens=emitted, finished=sum(req._finished for req, _, _ in live))
        self._counts["block_tokens_emitted"] += emitted

    def _block_experts(self, req: LLMRequest, start: int) -> np.ndarray:
        """The experts the block at ``start`` took in the pass that ran last, from the words beside its rows:
        [B, expert layers, k]. Read before another pass is dispatched (``_block_synced``): every pass writes them anew."""
        from ray_tpu.models.generate import MOE_CHOICE, unpack_experts

        bid, at = req._sched_table[start // self.block_size], start % self.block_size
        words = np.asarray(self._cache[MOE_CHOICE][..., bid, at : at + self._block])  # [(words,) expert layers, B]
        return unpack_experts(np.swapaxes(words, -1, -2), self.cfg)

    def _drawn_tokens(self, reqs: list, ids: np.ndarray, at: list) -> list[int]:
        """One ``llm.sample`` span a step over the host's share of its draws:
        the fetched ids of ``reqs`` (row ``at[i]`` of ``ids``) as Python ints.
        The draw itself ran inside the program (``draw_tokens``); ``top_k``
        counts the rows that engaged its conditional threshold search."""
        with self.spans.span(
            "llm.sample",
            rows=len(reqs),
            sampled=sum(r.temperature > 0.0 for r in reqs),
            top_k=sum(r.temperature > 0.0 and r.top_k > 0 for r in reqs),
        ):
            return ids[at].tolist()

    def _routed_experts(self, req: LLMRequest) -> np.ndarray:
        """The experts of every token ``req`` fed, from the words beside its
        blocks' rows: [tokens fed, expert layers, k]."""
        from ray_tpu.models.generate import MOE_CHOICE, unpack_experts

        fed = len(req.prompt) + len(req._sched_generated) - 1
        table = np.asarray(req._sched_table[: -(-fed // self.block_size)], np.int32)
        words = np.asarray(self._cache[MOE_CHOICE][..., table, :])  # [(words,) expert layers, blocks, Bs]
        words = words.reshape(*words.shape[:-2], -1)[..., :fed]
        return unpack_experts(np.swapaxes(words, -1, -2), self.cfg)  # [fed, expert layers, k]

    def _emit_token(self, req: LLMRequest, tok: int, t_emit_ns: int):
        """``t_emit_ns``: the start of the ``llm.emit`` span this runs in; it
        travels with the id, so a token's way back costs the scheduler no
        clock read of its own."""
        req._sched_generated.append(tok)
        req._sched_state = "decode"
        if req.t_first is None:
            req.t_first = time.monotonic()
        req._q.put(("token", (tok, t_emit_ns)))
        if len(req._sched_generated) >= req.max_new_tokens:
            self._finish(req)

    # --- terminal transitions ---

    def _preempt(self, victim: LLMRequest):
        LLM.preemptions += 1
        self._counts["preemptions"] += 1
        victim.preemptions += 1
        _flight.record(
            "llm_preempt", f"{victim.id}:n{len(victim._sched_generated)}"
        )
        self._release_blocks(victim)
        if victim._sched_slot is not None:
            self._slots[victim._sched_slot] = None
        victim._sched_slot = None
        victim._sched_state = "waiting"
        victim._sched_pos = 0
        with self._lock:
            self._waiting.appendleft(victim)  # resume first: FIFO-ish fairness

    def _finish(
        self,
        req: LLMRequest,
        error: str | None = None,
        cancelled=False,
        handoff: dict | None = None,
    ):
        if req._finished:
            return
        req._finished = True
        if req.return_routed_experts and error is None and not cancelled and handoff is None:
            req.routed_experts = self._routed_experts(req)
        if req.return_state and error is None and not cancelled and req._sched_slot is not None:
            # The step in flight has no row for it. A recurrent state, or (a short convolution keeps none) the carried rows.
            req.state = np.asarray(self._cache["state" if "state" in self._cache else "conv"][:, req._sched_slot])
        self._release_blocks(req)
        if req._sched_slot is not None and self._slots[req._sched_slot] is req:
            self._slots[req._sched_slot] = None
        req._sched_slot = None
        req._sched_state = "done"
        req.t_done = time.monotonic()
        if handoff is not None:
            outcome = "handoff"
            LLM.finished += 1
            self._counts["finished"] += 1
            req._q.put(("handoff", handoff))
        elif cancelled:
            outcome = "cancelled"
            LLM.cancelled += 1
            self._counts["cancelled"] += 1
            req._q.put(("done", "cancelled"))
        elif error is not None:
            outcome = "error"
            LLM.finished += 1
            self._counts["finished"] += 1
            req.error = error
            req._q.put(("error", error))
        else:
            outcome = "finished"
            LLM.finished += 1
            self._counts["finished"] += 1
            req._q.put(("done", "complete"))
        self.spans.end_request(req, outcome)
