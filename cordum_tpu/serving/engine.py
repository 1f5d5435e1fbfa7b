"""The continuous-batching loop (the serving subsystem's scheduler).

Lifecycle of an ``llm.generate`` session (docs/SERVING.md):

  * :meth:`ServingEngine.submit` parks the session in the **admission
    queue**; admission allocates its full worst-case page footprint
    (prompt + max_new_tokens) so an admitted session can never die
    mid-decode from cache pressure — exhaustion just delays admission;
  * admitted sessions join the step loop immediately: their prompts
    **prefill in chunks inside the mixed step**, riding the token-budget
    headroom left after the decode rows (the FlexNPU co-location policy,
    PAPERS.md, realized the Ragged Paged Attention way — prefill and
    decode share ONE device call instead of racing for the device lock
    from separate executor threads);
  * every step assembles one ragged batch — one decode row per prefilled
    session plus up to ``max_concurrent_prefills`` prompt chunks within
    the backend's flat token budget — runs ONE XLA call (the single
    compiled program), scatters tokens back, admits joiners and retires
    finishers; sessions join/leave mid-stream without perturbing each
    other's rows and without recompiling anything;
  * retirement (finish / cancel / failure) frees the session's pages back
    to the allocator and resolves the submit waiter.

Token streaming rides the session's ``on_tokens`` callback (the worker
publishes ``JobProgress`` packets with ``status_hint="stream"``); the
terminal ``JobResult`` carries the full token list for non-streaming
consumers.
"""
from __future__ import annotations

import asyncio
import statistics
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Optional

from ..infra import logging as logx
from ..infra.metrics import Metrics
from ..obs import startup
from ..obs.profiler import GC_PAUSES
from ..obs.tracer import Tracer
from ..protocol.types import OP_SERVING_PREFILL, SPAN_ERROR, Span
from ..utils.eager import eager, eager_gather
from ..utils.ids import fast_id
from .backend import STEP_PHASES, StepBackend, StepEntry, annotation, step_phase
from .modelspec import require_page_records, require_positional
from .pager import CacheExhausted, PageAllocator, SlotAllocator
from .prefixcache import PrefixCache, PrefixNode
from .tiering import SessionTiering

# on_tokens(new_tokens, n_generated, done) — the streaming sink
TokenSink = Callable[[list[int], int, bool], Awaitable[None]]

DEFAULT_MAX_SESSIONS = 8
DEFAULT_MAX_NEW_TOKENS = 64
# prefill chunks co-scheduled into one mixed step: more rows admit faster,
# but each chunk spends flat-buffer slots the decode rows also want
DEFAULT_MAX_CONCURRENT_PREFILLS = 2
# SLO classes whose prefill chunks take the step budget first
# (docs/ADMISSION.md §Serving)
INTERACTIVE_CLASSES = frozenset({"INTERACTIVE", "CRITICAL"})
# speculative decoding (docs/SERVING.md §Speculative decoding): default
# draft length cap, the per-session acceptance EWMA that throttles the
# next step's draft length, and the engine-level EWMA the capacity block
# publishes as spec_accept_rate
DEFAULT_DRAFT_K = 4
SPEC_EWMA_ALPHA = 0.4
SPEC_FLEET_ALPHA = 0.2
# step-cycle traces (docs/OBSERVABILITY.md §Serving spans and metrics): every
# cycle is stamped, and one becomes a ``step`` trace when this long has
# passed since the last kept cycle began, or when it took more than
# STEP_STALL_FACTOR times the median of the STEP_MEDIAN_WINDOW cycles before
# it — a stalled cycle is always kept, with the phase it stalled in
STEP_SAMPLE_PERIOD_NS = 250_000_000
STEP_STALL_FACTOR = 3.0
STEP_MEDIAN_WINDOW = 64
# a collector pause becomes a ``runtime.gc`` span (and keeps the cycle it fell
# in) when it is a generation-2 collection or lasted this long; shorter ones
# only add to ``ServingStats.gc_pause_seconds``
GC_SPAN_MIN_NS = 1_000_000


class SessionCancelled(Exception):
    """Session evicted by ``sys.job.cancel`` (queued, prefilling or
    decoding); the worker publishes an ordinary CANCELLED result."""


class SessionMigrated(Exception):
    """Session live-migrated to a peer worker (docs/SERVING.md §Migration,
    drain, and failover): the target owns the token stream and the terminal
    result now — the local waiter publishes NOTHING."""


class SessionRequeued(Exception):
    """Session handed back to the scheduler for failover (drain with no
    migration target, crashed decode loop): the worker publishes a
    non-terminal ``SESSION_REQUEUE`` result and the scheduler re-dispatches
    with the already-streamed tokens as a forced-decode prefix — bounded by
    the attempts counter, FAILED only past the cap."""


class SessionHibernated(Exception):
    """Session frozen whole and tiered into the host-RAM cold arena
    (docs/SERVING.md §Prefix cache and tiering): a later
    ``restore_hibernated`` on this worker owns the token stream and the
    terminal result — the local waiter publishes NOTHING (the live-
    migration contract, pointed at ourselves)."""


@dataclass
class GenRequest:
    """A decomposed ``llm.generate`` payload."""

    prompt: list[int]
    max_new_tokens: int = 16
    session_key: str = ""
    eos_token: Optional[int] = None
    stream: bool = True
    # SLO class (JobRequest.priority, stamped by the worker intake): batch
    # prefill chunks yield step-budget headroom to interactive ones
    # (docs/ADMISSION.md §Serving)
    job_class: str = "BATCH"
    # failover resume (LABEL_RESUME_TOKENS): tokens a previous worker
    # already generated and streamed for this job.  They prefill as a
    # forced-decode prefix (prompt + resume ride the chunked prefill path),
    # count toward max_new_tokens, and replay at offset 0 so stream
    # consumers deduping by offset see an exactly-once sequence.
    resume_tokens: list[int] = field(default_factory=list)


@dataclass
class ServingStats:
    admitted: int = 0
    retired: int = 0
    cancelled: int = 0
    failed: int = 0
    steps: int = 0
    decoded_tokens: int = 0  # generated tokens (decode rows + first tokens)
    prefill_tokens: int = 0  # prompt tokens fed through mixed-step chunks
    prefill_chunks: int = 0
    migrated_out: int = 0  # sessions live-migrated to a peer worker
    migrated_in: int = 0  # sessions adopted from a peer worker
    requeued: int = 0  # sessions handed back to the scheduler for failover
    prefix_hits: int = 0  # admissions that mapped cached shared-prefix pages
    prefix_misses: int = 0
    prefix_hit_tokens: int = 0  # prompt tokens whose prefill was skipped
    cow_copies: int = 0  # copy-on-write page duplications
    drafted_tokens: int = 0  # speculative tokens proposed into draft rows
    accepted_tokens: int = 0  # drafts verified and kept (bonus excluded)
    rolled_back_tokens: int = 0  # drafts rejected; write positions rolled back
    spec_steps: int = 0  # steps that carried at least one draft row
    hibernated_out: int = 0  # live sessions tiered whole to the cold arena
    restored_in: int = 0  # live sessions restored from the cold arena
    occupancy_sum: int = 0
    max_occupancy: int = 0
    admission_waits: int = 0  # admissions delayed by cache exhaustion
    # the attention's block walk, summed over steps: blocks read (up to the
    # step's longest live row) of the blocks the page tables hold
    attn_blocks_walked: int = 0
    attn_blocks_total: int = 0
    # table rows the walk's tiles gathered, a block of pages each, summed
    # over steps, by one full and one window layer together: x a block's
    # bytes x the layers of the kind, what the attention read (the by-head
    # kernel copies a block once a RUN of one row's tiles: 1 - gathered x
    # tile slots / computed is the share of tile-trips that rode a copy)
    attn_rows_gathered: int = 0
    # query slots those walks computed, a block each, and the FED slots among
    # them that needed their block: the rest is what the tiles pad (a tile's
    # empty slots, a short tile walked to its group's longest)
    attn_slots_computed: int = 0
    attn_slots_live: int = 0
    # bytes of the whole-row kind's pages behind a step's rows, summed over
    # steps: what the rows' cache is (all layers, every arena of the kind)
    kv_bytes_behind_rows: int = 0
    # a model with window layers (docs/SERVING.md §Two kinds of page): blocks
    # its window layers' walk read, summed over steps; ring slots written
    # again after a lap (a page's worth of the row fell out of the window);
    # peaks of the pages sessions held, by kind
    window_blocks_walked: int = 0
    window_pages_reused: int = 0
    kv_pages_held_full: int = 0
    kv_pages_held_window: int = 0
    # a model with recurrent state (docs/SERVING.md §The state slot): the
    # most state slots sessions held at once; rows that advanced their state
    # by ONE token, summed over steps (decode rows: a state read and written
    # for one token), and tokens fed by rows of more than one (prefill chunks)
    state_slots_peak: int = 0
    state_decode_rows: int = 0
    state_chunk_tokens: int = 0
    # what the model family's program counted of its own layers, summed over
    # steps under the names the family gives them (``ModelSpec.count_aux``:
    # an expert layer's assignments, a recurrence's rows, ...; the engine
    # knows none of them).  A name nobody counted reads 0
    model: Counter = field(default_factory=Counter)
    # stream packets the step loop handed to the sinks (a session's new
    # tokens of one step; replays of a carried prefix are not among them),
    # and those of them published with the NEXT step already on the device
    stream_packets: int = 0
    stream_packets_behind_step: int = 0
    # the loop's life outside its step cycles, since the start-up record
    # closed: seconds it stood parked (no session live: from the start of the
    # cycle that found nothing, through the last step's tokens told, to its
    # wake) and seconds it polled (sessions or pending work and nothing to
    # feed).  With the cycles' six phases they cover the loop's life
    parked_seconds: float = 0.0
    polled_seconds: float = 0.0
    # the collector's pauses the process saw since then (``obs/profiler.py``
    # ``GC_PAUSES``), every generation and length
    gc_pauses: int = 0
    gc_pause_seconds: float = 0.0
    # per-step wall time (seconds), capped ring for inter-token p50/p99
    step_seconds: deque = field(default_factory=lambda: deque(maxlen=4096))
    # submit → first sampled token (seconds), capped ring for TTFT p50
    # (resume/migrated-in sessions excluded: their first token belongs to
    # a previous worker's clock)
    ttft_seconds: deque = field(default_factory=lambda: deque(maxlen=4096))

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0


@dataclass
class _TtftClock:
    """A locally born session's way to its first token: ``serving.queue``
    (submit → admitted) then ``serving.prefill`` (admitted → the step that
    sampled the first token has returned), contiguous on ONE clock — the
    monotonic one, anchored on the wall at submit — so the two always sum
    to the session's ``ttft_seconds`` entry."""

    pending_ahead: int  # sessions queued before it at submit
    waits_at_submit: int  # stats.admission_waits when it was queued
    admitted_at: float = 0.0  # monotonic
    chunks: int = 0  # prefill chunks fed up to the first token
    steps: int = 0  # steps ridden up to the first token


@dataclass
class _Session:
    job_id: str
    req: GenRequest
    future: asyncio.Future
    on_tokens: Optional[TokenSink] = None
    trace_id: str = ""
    parent_span_id: str = ""
    pages: list[int] = field(default_factory=list)
    # the session's ring of window-layer pages (a model with window layers)
    window_pages: list[int] = field(default_factory=list)
    # the session's state slot (a model with recurrent state); 0: none
    state_slot: int = 0
    pos: int = 0  # sequence positions cached so far
    prefill_pos: int = 0  # prompt tokens fed so far (== pos until prefilled)
    last_token: int = 0
    out_tokens: list[int] = field(default_factory=list)
    cancelled: bool = False
    # frozen = mid-migration: the step loop must not advance this session
    # (decode pauses only for the final freeze-and-delta chunk)
    frozen: bool = False
    # post-prefill hand-off (docs/SERVING.md §Disaggregation): the
    # on_prefill_done hook fires at most once per session
    handoff_signaled: bool = False
    # governor immunity: a migrated-in session may not be rebalanced again
    # before this monotonic stamp (the anti-ping-pong cooldown)
    immune_until: float = 0.0
    # speculative decoding: the session's acceptance EWMA (throttles the
    # next step's draft length; optimistic start so drafts flow at once)
    # and the tokens the drafter planned for the upcoming step
    accept_ewma: float = 1.0
    draft_plan: list[int] = field(default_factory=list)
    # stream packets of this session that a step booked and nobody was told
    # yet: its future does not resolve, and it is not quiesced, before 0
    unsent: int = 0
    enqueued_at: float = field(default_factory=time.monotonic)
    enqueued_ns: int = field(default_factory=time.time_ns)  # same instant, wall
    # open until the first token; None for migrated-in, resumed and restored
    # sessions (their first token belongs to a previous worker's clock)
    ttft: Optional[_TtftClock] = None
    # ``_first_token``'s stamp (monotonic) until the session's first stream
    # packet has been told (``serving.first_packet``); 0.0 otherwise
    first_token_at: float = 0.0

    @property
    def prefill_seq(self) -> list[int]:
        """What prefill must feed: the prompt plus the forced-decode resume
        prefix MINUS its last token (failover replay, docs/SERVING.md).
        The final resume token stays ``last_token``: the first post-resume
        step is then an ordinary decode row feeding it at the next
        position — the exact state a live-migrated session resumes from,
        so the continuation token is sampled with decode semantics, not a
        prefill-completion sample."""
        seq = self.req.prompt + self.req.resume_tokens
        return seq[:-1] if self.req.resume_tokens else seq

    @property
    def prefilled(self) -> bool:
        return self.prefill_pos >= len(self.prefill_seq)

    @property
    def done(self) -> bool:
        if len(self.out_tokens) >= self.req.max_new_tokens:
            return True
        eos = self.req.eos_token
        return eos is not None and bool(self.out_tokens) and self.out_tokens[-1] == eos


class ServingEngine:
    """One per worker; owns the allocator, the session table and the loop."""

    def __init__(
        self,
        backend: StepBackend,
        *,
        run_blocking: Callable[..., Awaitable[Any]],
        max_sessions: int = DEFAULT_MAX_SESSIONS,
        max_new_tokens_cap: int = DEFAULT_MAX_NEW_TOKENS,
        max_concurrent_prefills: int = DEFAULT_MAX_CONCURRENT_PREFILLS,
        metrics: Optional[Metrics] = None,
        tracer: Optional[Tracer] = None,
        capacity: Optional[Any] = None,
        handoff_threshold_tokens: int = 0,
        migrate_in_cooldown_s: float = 30.0,
        prefix_cache: Optional[bool] = None,
        hibernate_after_s: float = 0.0,
        speculative: Optional[bool] = False,
        draft_k: int = DEFAULT_DRAFT_K,
        drafter: Optional[Callable[[list[int], int], list[int]]] = None,
    ) -> None:
        self.backend = backend
        self.run_blocking = run_blocking  # worker.run_in_executor
        # post-prefill hand-off (docs/SERVING.md §Disaggregation): the owner
        # (the worker) sets this to a callable(job_id); the loop invokes it
        # once per session when its prompt finishes prefilling — or earlier,
        # once prefill crosses handoff_threshold_tokens (>0) — so a
        # prefill-roled worker can ship the session to a decode worker
        # while the KV pages are hot
        self.on_prefill_done: Optional[Callable[[str], None]] = None
        self.handoff_threshold_tokens = max(0, handoff_threshold_tokens)
        # governor anti-ping-pong: sessions adopted via install_session are
        # immune to pick_rebalance_sessions for this window (drain ignores
        # it — a draining worker must move everything)
        self.migrate_in_cooldown_s = max(0.0, migrate_in_cooldown_s)
        # capacity observatory (obs/capacity.py): each ragged step reports
        # delivered tokens at the static flat-buffer bucket, with warmup
        # compiles flagged so steady-state rows exclude them
        self.capacity = capacity
        self.max_sessions = max(1, max_sessions)
        self.max_new_tokens_cap = max(1, max_new_tokens_cap)
        self.max_concurrent_prefills = max(1, max_concurrent_prefills)
        self.metrics = metrics
        self.tracer = tracer
        # the backend's static page-table width caps a session's lifetime
        # footprint; anything longer must be rejected at submit (the arena
        # may hold far more pages than one table row can address)
        self.max_context = backend.max_context
        # the flat token buffer bounds decode rows + prefill chunk tokens
        # per step; every admitted session must at least fit a decode row
        self.step_tokens = backend.max_batch_tokens
        self.max_sessions = min(
            self.max_sessions, backend.max_seqs, self.step_tokens,
            # a session of a model with recurrent state holds a slot of its own
            *([backend.state_slots - 1] if backend.state_slots else []),
        )
        self.allocator = PageAllocator(backend.num_pages, backend.page_size)
        # a model with window layers keeps a second kind of page, in a ring
        # of ``ring_pages`` per session: its own pool, reservations and
        # refcounts (docs/SERVING.md §Two kinds of page)
        self.ring_pages = backend.ring_pages
        self.window_allocator: Optional[PageAllocator] = (
            PageAllocator(backend.num_window_pages, backend.page_size)
            if self.ring_pages else None
        )
        # what assumes ONE kind of page per session — the prefix cache,
        # hibernation, live migration — is off for such a model
        self.kv_whole_row = backend.kv_whole_row
        # what carries a page as K and V records by head — hibernation and
        # live migration — is off for a model whose page is another thing (a
        # latent page); prefix sharing is not: copying a page needs no record
        self.kv_by_head = backend.kv_by_head
        # what shares, re-feeds or carries POSITIONS — the prefix cache,
        # speculation's verify rows, hibernation, live migration — refuses a
        # model that also keeps recurrent state: a session of it holds a
        # state slot beside its pages, from admission to retirement, never
        # shared and never copied (docs/SERVING.md §The state slot).  Asked
        # for by name (``prefix_cache=True``, ``speculative=True``) they are
        # refused; None is "on where the model allows" (the worker's defaults),
        # resolved here and nowhere else
        self.kv_positional = backend.kv_positional
        self.state_allocator: Optional[SlotAllocator] = (
            SlotAllocator(backend.state_slots) if backend.state_slots else None
        )
        if not self.kv_positional:
            if prefix_cache:
                require_positional(False, "the prefix cache")
            if speculative:
                require_positional(False, "speculative decoding (its verify rows re-feed positions)")
            if hibernate_after_s > 0:
                require_positional(False, "hibernation")
            if prefix_cache is None or speculative is None:
                logx.info("the prefix cache and the drafter stay off where they were left to the "
                          "default: this model keeps recurrent state in per-session slots "
                          "(kv_positional is false)")
        if prefix_cache is None:
            prefix_cache = self.kv_positional
        if speculative is None:
            speculative = self.kv_positional
        self.kv_portable = self.kv_whole_row and self.kv_by_head and self.kv_positional
        # prefix cache + session tiering (docs/SERVING.md §Prefix cache and
        # tiering): the radix index over cached full-page prefixes, and the
        # hibernate/restore machinery that tiers idle resident state to the
        # host-RAM cold arena.  hibernate_after_s <= 0 disables the sweep
        # (the cache still shares; pressure is handled by LRU eviction).
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.allocator, metrics=metrics)
            if prefix_cache and self.kv_whole_row else None
        )
        self.tiering: Optional[SessionTiering] = (
            SessionTiering(
                self.prefix,
                hibernate_after_s=hibernate_after_s,
                export_page=self._export_prefix_page,
                metrics=metrics,
            )
            if self.prefix is not None and self.kv_portable else None
        )
        # speculative decoding (docs/SERVING.md §Speculative decoding):
        # the self-speculative drafter proposes k tokens per decoding
        # session per step; verification rides the same ragged program as
        # prefill-shaped draft rows with per-position sampling.  Off, no
        # draft row is ever assembled.
        self.speculative = bool(speculative)
        self.draft_k = max(1, int(draft_k or DEFAULT_DRAFT_K))
        self._drafter = drafter or self._ngram_draft
        # engine-level acceptance EWMA — the capacity block publishes it
        # as spec_accept_rate so the placer can route speculable traffic
        self.spec_accept_ewma = 0.0
        self._tiering_task: Optional[asyncio.Task] = None
        self.stats = ServingStats()
        self._pending: deque[_Session] = deque()
        self._active: dict[str, _Session] = {}
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._closed = False
        # job ids riding the step currently on the device: a migration
        # freeze is complete only once the in-flight step (which may still
        # produce one token for the session) has scattered its results and
        # that token's packet is out (``_Session.unsent``)
        self._in_step: frozenset[str] = frozenset()
        # the last step's stream packets ``(session, tokens, count, done)``,
        # in row order, and the retired sessions whose futures wait for
        # them: both are told once the NEXT step is on the device (or
        # before the loop parks), never between two steps.  The lock makes
        # ``stop()`` wait for a publish the loop is in the middle of
        self._unsent: list[tuple[_Session, list[int], int, bool]] = []
        self._unresolved: list[tuple[_Session, Optional[BaseException]]] = []
        self._publishing = asyncio.Lock()
        self._packets_at_close = (0, 0)  # the two stream counters, last cycle
        # set, through the loop, by the executor thread once the step it was
        # handed is fed to the device (``StepBackend.on_dispatched``) or has
        # returned: from then on the loop may hold the interpreter
        self._fed = asyncio.Event()
        self._tell_fed: Callable[[], None] = lambda: None
        self._crash_publish: Optional[asyncio.Task] = None
        # names this worker's step traces (``step-<worker_id>-<n>``); the
        # owning worker sets it when it attaches the engine
        self.worker_id = ""
        # finished spans wait here until a step is on the device (or the
        # loop parks): no publish sits between one step and the next
        self._spans: list[Span] = []
        self._cycle_ns: deque[int] = deque(maxlen=STEP_MEDIAN_WINDOW)
        self._kept_cycle_ns = 0  # start of the last cycle kept as a trace
        self._startup_told = False  # the start-up record went out (or was not ours)
        self._poll: Optional[tuple[str, int]] = None  # the open poll: (reason, since ns)
        # the collector's pauses (``GC_PAUSES``) are read from the moment the
        # start-up record is told, past this ordinal at the last take
        self._gc_seen = 0

    # ------------------------------------------------------------------
    def parts(self, payload: Any) -> Optional[GenRequest]:
        """Decompose a job payload; None = not a serving job (the worker
        keeps its ordinary handler path)."""
        from ..protocol.types import SERVING_OPS

        if not isinstance(payload, dict) or payload.get("op") not in SERVING_OPS:
            return None
        tokens = payload.get("tokens")
        if not (
            isinstance(tokens, list) and tokens
            and all(isinstance(t, int) for t in tokens)
        ):
            return None
        try:
            max_new = int(payload.get("max_new_tokens", 16) or 16)
        except (TypeError, ValueError):
            # malformed payload is not a session: fall through to the
            # handler path, which raises the op's own descriptive error
            return None
        eos = payload.get("eos_token")
        return GenRequest(
            prompt=tokens,
            max_new_tokens=max(1, min(max_new, self.max_new_tokens_cap)),
            session_key=str(payload.get("session_id", "") or ""),
            eos_token=int(eos) if isinstance(eos, int) else None,
            stream=bool(payload.get("stream", True)),
        )

    # ------------------------------------------------------------------
    @property
    def session_count(self) -> int:
        return len(self._pending) + len(self._active)

    def queue_depth(self) -> int:
        return len(self._pending)

    def active_sessions(self) -> int:
        return len(self._active)

    # ------------------------------------------------------------------
    async def submit(
        self,
        gen: GenRequest,
        *,
        job_id: str,
        trace_id: str = "",
        parent_span_id: str = "",
        on_tokens: Optional[TokenSink] = None,
    ) -> dict[str, Any]:
        """Queue a session and await its completed generation."""
        if self._closed:
            raise RuntimeError("serving engine is stopped")
        total = len(gen.prompt) + gen.max_new_tokens
        if total > self.max_context:
            # beyond the backend's static page-table width: prefill would
            # silently truncate and the session would poison its step —
            # fail this job alone, before it becomes a session
            raise ValueError(
                f"request spans {total} tokens (prompt {len(gen.prompt)} + "
                f"{gen.max_new_tokens} new); backend max_context is "
                f"{self.max_context}"
            )
        footprint = self.allocator.pages_for(total)
        if footprint > self.allocator.capacity:
            raise ValueError(
                f"request needs {footprint} KV pages; cache holds "
                f"{self.allocator.capacity}"
            )
        if self.window_allocator is not None and (
            self._ring_for(total) > self.window_allocator.capacity
        ):
            raise ValueError(
                f"request needs {self._ring_for(total)} window-layer KV pages; "
                f"cache holds {self.window_allocator.capacity}"
            )
        sess = _Session(
            job_id=job_id, req=gen,
            future=asyncio.get_running_loop().create_future(),
            on_tokens=on_tokens if gen.stream else None,
            trace_id=trace_id, parent_span_id=parent_span_id,
        )
        if gen.resume_tokens:
            # forced-decode resume: the prefix counts as already-generated
            # output; prefill feeds prompt + prefix and decoding continues
            # from the prefix's last token
            sess.out_tokens = list(gen.resume_tokens)
            sess.last_token = gen.resume_tokens[-1]
        else:
            sess.ttft = _TtftClock(
                pending_ahead=len(self._pending),
                waits_at_submit=self.stats.admission_waits,
            )
        self._pending.append(sess)
        self._ensure_loop()
        self._wake.set()
        tokens = await sess.future
        return self.result_doc(gen, tokens)

    @staticmethod
    def result_doc(gen: GenRequest, tokens: list[int]) -> dict[str, Any]:
        """The terminal result payload for a finished generation — shared by
        :meth:`submit` and the migrated-session adoption path."""
        return {
            "tokens": tokens,
            "n_tokens": len(tokens),
            "session_key": gen.session_key,
            "finish_reason": (
                "eos" if gen.eos_token is not None and tokens
                and tokens[-1] == gen.eos_token else "length"
            ),
        }

    # ------------------------------------------------------------------
    def cancel(self, job_id: str) -> bool:
        """Evict a session wherever it is: admission queue (pages never
        allocated) or the step loop — prefilling or decoding, the pages are
        freed by the loop on its next tick.  Returns False when the job is
        not a live session."""
        for i, sess in enumerate(self._pending):
            if sess.job_id == job_id:
                del self._pending[i]
                # _retire keeps stats and the retirement metric in step
                # (pages were never allocated; free() is a no-op here)
                self._retire(sess, error=SessionCancelled(job_id))
                return True
        sess = self._active.get(job_id)
        if sess is not None:
            sess.cancelled = True  # the loop retires + frees pages
            self._wake.set()
            return True
        return False

    # ------------------------------------------------------------------
    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.ensure_future(self._decode_loop())
            self._loop_task.add_done_callback(self._on_loop_done)
        if (
            self.tiering is not None and self.tiering.hibernate_after_s > 0
            and (self._tiering_task is None or self._tiering_task.done())
        ):
            self._tiering_task = asyncio.ensure_future(self._tiering_loop())

    async def _tiering_loop(self) -> None:
        """Periodic hibernate sweep — its own task because idle resident
        conversations are exactly the ones generating no steps: the decode
        loop is parked on its wake event while they cool down."""
        assert self.tiering is not None
        interval = max(0.05, min(1.0, self.tiering.hibernate_after_s / 4))
        while not self._closed:
            await asyncio.sleep(interval)
            if self._closed:
                return
            try:
                await self.tiering.sweep()
            except Exception as e:  # noqa: BLE001 - sweep is best-effort
                logx.warn("hibernate sweep failed", err=str(e))

    async def _export_prefix_page(self, page: int) -> Optional[dict]:
        """One full arena page as a PR 12 migration record — the tiering
        sweep's export half."""
        recs = await self.run_blocking(
            self.backend.export_kv, [page], 0, self.allocator.page_size)
        return recs[0] if recs else None

    def _on_loop_done(self, task: asyncio.Task) -> None:
        """Step failures are handled inside the loop; anything that still
        escapes must not strand live sessions on never-resolving futures —
        hand them back to the scheduler for failover (each publishes a
        non-terminal SESSION_REQUEUE result; the attempts counter bounds the
        retries, so a deterministic crasher still ends FAILED past the cap)
        and let the next submit restart the loop."""
        if task.cancelled() or self._closed:
            return
        exc = task.exception()
        if exc is None:
            return
        logx.warn("decode loop crashed; requeueing live sessions", err=str(exc))
        for sess in [*self._pending, *self._active.values()]:
            self._retire(sess, error=SessionRequeued(
                f"decode loop crashed: {exc}"
            ))
        self._pending.clear()
        if self._unsent:
            # the crash fell between a step's bookkeeping and its publish:
            # the tokens still go out, and the futures behind them resolve
            self._crash_publish = asyncio.ensure_future(self._publish_unsent(False))

    def _gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.serving_sessions.set(float(len(self._active)))
            self.metrics.serving_kv_pages_in_use.set(float(self.allocator.used_pages))
            if self.state_allocator is not None:
                self.metrics.serving_state_slots.set(float(self.state_allocator.used))

    def _require_records(self, feature: str) -> None:
        """Refuse ``feature``, which carries a session as K and V page
        records, for a model with recurrent state, window rings or latent
        pages — each by the capability it lacks."""
        require_positional(self.kv_positional, feature)
        require_page_records(self.kv_whole_row, self.kv_by_head, feature)

    def _ring_for(self, n_tokens: int) -> int:
        """Window-layer pages a session of ``n_tokens`` positions holds: its
        whole row while that is shorter than the ring, the ring after."""
        return min(self.ring_pages, self.allocator.pages_for(n_tokens))

    async def _admit(self) -> None:
        """Move pending sessions straight into the step loop while pages
        and session slots allow; FIFO so exhaustion delays but never
        reorders admission.  An admitted session needs no separate prefill
        phase — its prompt chunks ride the next steps' token budget.

        Prefix-cache hook (docs/SERVING.md §Prefix cache and tiering): the
        longest cached page-aligned prefix of the prompt maps its physical
        pages straight into the new session's table — prefill starts at
        the divergence point.  Cold nodes on the hit path restore from the
        host-RAM arena first (the hibernate restore), and exhaustion
        LRU-evicts zero-refcount cached prefixes before the head-of-line
        admission gives up and waits."""
        while self._pending and len(self._active) < self.max_sessions:
            sess = self._pending[0]
            if sess.cancelled:
                self._pending.popleft()
                self._retire(sess, error=SessionCancelled(sess.job_id))
                continue
            footprint = self.allocator.pages_for(
                len(sess.req.prompt) + sess.req.max_new_tokens
            )
            shared: list[int] = []
            hit_tokens = 0
            if self.prefix is not None and not sess.req.resume_tokens:
                nodes = await self._restore_nodes(
                    self.prefix.match(sess.prefill_seq)
                )
                if self._closed:
                    break  # stop() raced the restore await
                if sess.cancelled:
                    continue  # loop head pops + retires it
                # keep only the unbroken warm head of the path (a restore
                # may have truncated it, or an eviction raced the await)
                for node in nodes:
                    if node.dropped or not node.warm:
                        break
                    shared.append(node.page)
                # at least one token must be fed through prefill so the
                # completing chunk has a position to sample from; a hit
                # ending exactly at the prompt end re-feeds the final
                # token into shared territory (the CoW guard copies that
                # page before the step writes it)
                hit_tokens = min(
                    len(shared) * self.allocator.page_size,
                    len(sess.prefill_seq) - 1,
                )
                if hit_tokens < 1:
                    shared, hit_tokens = [], 0
            try:
                pages = self._alloc_with_evict(
                    sess.job_id, footprint - len(shared), shared
                )
                if self.window_allocator is not None:
                    # reserved per kind.  Only the whole-row kind can run
                    # out: the backend's window pool holds a whole ring for
                    # each of its ``max_seqs`` rows, ``max_sessions`` is at
                    # most that, and a session holds at most one ring
                    sess.window_pages = self.window_allocator.alloc(
                        sess.job_id, self._ring_for(
                            len(sess.req.prompt) + sess.req.max_new_tokens))
                    # the allocators keep the peaks; the stats mirror them
                    self.stats.kv_pages_held_full = (
                        self.allocator.stats.peak_pages_in_use)
                    self.stats.kv_pages_held_window = (
                        self.window_allocator.stats.peak_pages_in_use)
            except CacheExhausted:
                self.stats.admission_waits += 1
                break  # head-of-line waits for a retirement to free pages
            if self.state_allocator is not None:
                # cannot run out: a slot a session row, and ``max_sessions``
                # is at most the slots (the window kind's argument)
                sess.state_slot = self.state_allocator.alloc(sess.job_id)
                self.stats.state_slots_peak = self.state_allocator.peak_in_use
            self._pending.popleft()
            sess.pages = pages
            if hit_tokens > 0:
                # the skipped positions' K/V already sits in the shared
                # pages (identical token prefix ⇒ identical K/V — the
                # radix path IS the key); chunked prefill picks up at the
                # divergence point via prefill_pos
                sess.prefill_pos = hit_tokens
                sess.pos = hit_tokens
                self.stats.prefix_hits += 1
                self.stats.prefix_hit_tokens += hit_tokens
                self.prefix.stats.hits += 1
                self.prefix.stats.hit_tokens += hit_tokens
                if self.metrics is not None:
                    self.metrics.serving_prefix.inc(outcome="hit")
                    self.metrics.serving_prefix_tokens.inc(float(hit_tokens))
            elif self.prefix is not None and not sess.req.resume_tokens:
                self.stats.prefix_misses += 1
                self.prefix.stats.misses += 1
                if self.metrics is not None:
                    self.metrics.serving_prefix.inc(outcome="miss")
            if self.tiering is not None and sess.req.session_key:
                self.tiering.touch(sess.req.session_key)
            self._active[sess.job_id] = sess
            self.stats.admitted += 1
            if self.metrics is not None:
                self.metrics.serving_admitted.inc()
            self._queue_closed(sess, hit_tokens)
            if sess.out_tokens and sess.on_tokens is not None:
                # failover resume: replay the already-streamed prefix at
                # offset 0 — consumers dedupe by offset, so a client that
                # saw the original stream skips it and one that missed
                # packets in the crash window backfills
                asyncio.ensure_future(self._emit(
                    sess, list(sess.out_tokens), len(sess.out_tokens), sess.done))
            if sess.done:
                # the crash landed after the final token: nothing left to
                # decode — finish straight from the resume prefix
                self._retire(sess)

    def _alloc_with_evict(
        self, owner: str, n_fresh: int, shared: list[int]
    ) -> list[int]:
        """Admission alloc with the exhaustion hook: LRU-evict cached
        prefixes only the cache still references to cover the shortfall,
        then retry once.  The hit path's own pages are shielded with an
        extra reference while evicting, so the eviction scan can never
        free a page this very admission is about to map."""
        try:
            return self.allocator.alloc(owner, n_fresh, shared=shared)
        except CacheExhausted:
            if self.prefix is None:
                raise
            need = n_fresh - self.allocator.free_pages
            if shared:
                self.allocator.retain(shared)
            try:
                if self.prefix.evict(need) < need:
                    raise
                return self.allocator.alloc(owner, n_fresh, shared=shared)
            finally:
                if shared:
                    self.allocator.release(shared)

    async def _restore_nodes(
        self, nodes: list[PrefixNode]
    ) -> list[PrefixNode]:
        """Re-warm the cold nodes on a matched path (hibernate restore):
        allocate a fresh page, scatter the host-RAM record back, promote.
        The path truncates at the first node that cannot restore
        (exhaustion even after eviction, or an eviction racing the
        scatter).  The pause — what the turn waits before its
        prefill can start — feeds
        ``cordum_serving_hibernate_pause_seconds``."""
        out: list[PrefixNode] = []
        t0 = None
        for node in nodes:
            if node.dropped:
                break
            if node.warm:
                out.append(node)
                continue
            if node.record is None or self.prefix is None:
                break
            if t0 is None:
                t0 = time.monotonic()
            try:
                (page,) = self.allocator.alloc_raw(1)
            except CacheExhausted:
                if self.prefix.evict(1) < 1:
                    break
                try:
                    (page,) = self.allocator.alloc_raw(1)
                except CacheExhausted:
                    break
            try:
                await self.run_blocking(
                    self.backend.import_kv, [page], [dict(node.record, i=0)])
            except Exception as e:  # noqa: BLE001 - keep the record, skip the hit
                self.allocator.release([page])
                logx.warn("prefix restore failed", err=str(e))
                break
            if node.dropped:
                self.allocator.release([page])
                break
            self.prefix.promote(node, page)
            if self.tiering is not None:
                self.tiering.stats.restored_pages += 1
            out.append(node)
        if t0 is not None and self.metrics is not None:
            self.metrics.serving_hibernate_pause.observe(time.monotonic() - t0)
        return out

    # ------------------------------------------------------------------
    # the flight recorder's serving side (docs/OBSERVABILITY.md §Serving
    # spans and metrics): boundaries are stamped always, histograms observed
    # always, Span objects built only while someone listens, and published
    # only by _flush_spans
    # ------------------------------------------------------------------
    def _ttft_span(
        self, name: str, sess: _Session, start: float, end: float,
        attrs: dict[str, str],
    ) -> None:
        """Buffer one of the two per-request spans on the request's own
        trace, under the worker's ``execute`` span; ``start``/``end`` are
        monotonic readings, placed on the wall by the submit anchor."""
        tr = self.tracer
        if tr is None or not sess.trace_id or not tr.listening():
            return

        def wall_us(t: float) -> int:
            return (sess.enqueued_ns + int((t - sess.enqueued_at) * 1e9)) // 1000

        self._spans.append(tr.record(
            name, trace_id=sess.trace_id, parent_span_id=sess.parent_span_id,
            start_us=wall_us(start), end_us=wall_us(end), attrs=attrs,
        ))

    def _queue_closed(self, sess: _Session, hit_tokens: int) -> None:
        """``_admit`` moved a locally born session into the step loop."""
        clk = sess.ttft
        if clk is None:
            return
        clk.admitted_at = now = time.monotonic()
        if self.metrics is not None:
            self.metrics.serving_queue.observe(now - sess.enqueued_at)
        self._ttft_span("serving.queue", sess, sess.enqueued_at, now, {
            "pending_ahead": str(clk.pending_ahead),
            "admission_waits": str(
                self.stats.admission_waits - clk.waits_at_submit),
            "prefix_hit_tokens": str(hit_tokens),
        })

    def _first_token(self, sess: _Session) -> None:
        """The step that sampled the session's first token has returned:
        TTFT, on the clock the two spans share."""
        now = time.monotonic()
        self.stats.ttft_seconds.append(now - sess.enqueued_at)
        clk, sess.ttft = sess.ttft, None
        if clk is None:
            return
        if self._startup_told and sess.on_tokens is not None:
            sess.first_token_at = now
        if self.metrics is not None:
            self.metrics.serving_prefill.observe(now - clk.admitted_at)
        self._ttft_span("serving.prefill", sess, clk.admitted_at, now, {
            "prompt_tokens": str(len(sess.req.prompt)),
            "chunks": str(clk.chunks),
            "steps": str(clk.steps),
        })

    def _cycle_closed(
        self, n_step: int, marks: list[int], attrs: dict[str, str]
    ) -> None:
        """One step cycle ended.  ``marks`` are the loop's own stamps
        ``[cycle start, step handed over, step returned, cycle end]``; the
        backend's five lie between the middle two.  Six contiguous phases:
        the histogram sees every cycle, the flight recorder the kept ones."""
        c0, handed, returned, c1 = marks
        bounds = (c0, *self.backend.last_phases, c1)
        # the two stamps inside a phase: the result ready on the device
        # (inside ``wait``; None from a backend that does not say) and the
        # loop awake again (inside ``emit``)
        ready = self.backend.last_ready_ns
        if any(a > b for a, b in zip(bounds, bounds[1:])):
            # the stamps are wall-clock and the wall may step backwards:
            # the whole call then reads as ``wait``, from the loop's own stamps
            bounds = (c0, handed, handed, handed, returned, returned, c1)
            ready = None
        if self.metrics is not None:
            for name, a, b in zip(STEP_PHASES, bounds, bounds[1:]):
                self.metrics.serving_step_phase.observe((b - a) / 1e9, phase=name)
        dur = c1 - c0
        recent = self._cycle_ns
        pauses = self._gc_taken()
        keep = (
            c0 - self._kept_cycle_ns >= STEP_SAMPLE_PERIOD_NS
            or "compile_ms" in attrs  # the compiler ran: never in steady state
            or (len(recent) == recent.maxlen
                and dur > STEP_STALL_FACTOR * statistics.median(recent))
            or bool(pauses)  # the collector held the process: kept with its cause
        )
        recent.append(dur)
        if not keep:
            return
        self._kept_cycle_ns = c0
        tr = self.tracer
        if tr is None or not tr.listening():
            return
        trace_id = f"step-{self.worker_id}-{n_step}"
        root_id = fast_id()
        ids = {name: fast_id() for name in STEP_PHASES}
        us = [b // 1000 for b in bounds]
        # children first: the collector decides a trace's retention when
        # its root lands
        for name, a, b in zip(STEP_PHASES, us, us[1:]):
            self._spans.append(tr.record(
                f"step.{name}", trace_id=trace_id, span_id=ids[name],
                parent_span_id=root_id, start_us=a, end_us=b,
            ))
        if self._startup_told:
            # what lies INSIDE a phase, under names that are no ``step*``
            # (a reader counts a cycle's seven): the result's way back to the
            # host inside ``wait``, the loop's wake-up inside ``emit``
            if ready is not None and bounds[3] <= ready <= bounds[4]:
                self._spans.append(tr.record(
                    "wait.fetch", trace_id=trace_id, parent_span_id=ids["wait"],
                    start_us=ready // 1000, end_us=us[4],
                ))
            if bounds[5] <= returned <= bounds[6]:
                self._spans.append(tr.record(
                    "emit.wake", trace_id=trace_id, parent_span_id=ids["emit"],
                    start_us=us[5], end_us=returned // 1000,
                ))
            if pauses:
                self._gc_spans(tr, pauses, trace_id, root_id)
                attrs["gc_ms"] = f"{sum(b - a for _, a, b, _ in pauses) / 1e6:.3f}"
                attrs["gc_gen"] = str(max(gen for _, _, _, gen in pauses))
        self._spans.append(tr.record(
            "step", trace_id=trace_id, span_id=root_id,
            start_us=us[0], end_us=us[-1], attrs=attrs,
        ))

    def _gc_taken(self) -> list[tuple[int, int, int, int]]:
        """The collector's pauses since the last take, counted into the
        stats; returns those worth a span: a generation-2 collection, or one
        of ``GC_SPAN_MIN_NS`` and more.  Nothing before the start-up record
        was told."""
        if not self._startup_told or GC_PAUSES.count == self._gc_seen:
            return []
        new = GC_PAUSES.since(self._gc_seen)
        if not new:
            return []
        self._gc_seen = new[-1][0]
        self.stats.gc_pauses += len(new)
        self.stats.gc_pause_seconds += sum(b - a for _, a, b, _ in new) / 1e9
        return [p for p in new if p[3] >= 2 or p[2] - p[1] >= GC_SPAN_MIN_NS]

    def _gc_spans(
        self, tr: Tracer, pauses: list[tuple[int, int, int, int]], trace_id: str,
        parent_span_id: str,
    ) -> None:
        """``runtime.gc`` spans of ``pauses`` on the trace of the interval
        that took them (a kept cycle's, a park's or a poll's)."""
        for _, a, b, gen in pauses:
            self._spans.append(tr.record(
                "runtime.gc", trace_id=trace_id, parent_span_id=parent_span_id,
                start_us=a // 1000, end_us=b // 1000, attrs={"generation": str(gen)},
            ))

    def _idle_closed(self, state: str, since_ns: int, end_ns: int) -> None:
        """The loop ran no step cycle from ``since_ns`` to ``end_ns``:
        ``state`` is ``parked`` (no session live: from the start of the cycle
        that found nothing, through the last step's tokens told and the
        flush, to its wake) or a poll's reason, ``pages`` (pending work that
        waits for pages) or ``budget`` (live sessions and no row to feed).
        Seconds and a counter always, a trace ``loop-<worker_id>-<steps
        done>`` of one span while someone listens; counted from the moment
        the start-up record was told."""
        if not self._startup_told:
            return
        secs = max(0, end_ns - since_ns) / 1e9
        parked = state == "parked"
        if parked:
            self.stats.parked_seconds += secs
        else:
            self.stats.polled_seconds += secs
        if self.metrics is not None:
            self.metrics.serving_loop_idle.inc(secs, state="parked" if parked else "poll")
        pauses = self._gc_taken()
        tr = self.tracer
        if tr is None or not tr.listening():
            return
        trace_id = f"loop-{self.worker_id}-{self.stats.steps}"
        span_id = fast_id()
        self._gc_spans(tr, pauses, trace_id, span_id)
        self._spans.append(tr.record(
            "serving.parked" if parked else "serving.poll", trace_id=trace_id,
            span_id=span_id, start_us=since_ns // 1000, end_us=end_ns // 1000,
            attrs={} if parked else {"reason": state},
        ))

    async def _unfed(self, since_ns: int) -> int:
        """The cycle that began at ``since_ns`` found nothing to feed: tell
        what the last step left untold (there is no next step to hide it
        behind), then park until woken (nothing live, nothing pending) or
        poll (``pages``: pending work and no session live, pages freeing;
        ``budget``: every live row parked past the budget or frozen).  The
        interval is the park's or the poll's from ``since_ns`` on, held under
        the host annotation ``cordum.serving.parked`` / ``.poll``; a poll
        stays open over the cycles that feed nothing and ends where the next
        cycle that feeds begins (or where a park or another reason does).
        Returns the stamp the next cycle starts at: a park's wake, 0 after a
        poll (the cycle stamps its own start, the poll's end if it feeds)."""
        state = "budget" if self._active else "pages" if self._pending else "parked"
        if self._poll is not None and self._poll[0] != state:
            self._idle_closed(*self._poll, since_ns)
            self._poll = None
        parked = state == "parked"
        with (annotation("cordum.serving.parked") if parked
              else annotation("cordum.serving.poll", reason=state)):
            await self._publish_unsent(behind_step=False)
            if not self._active:
                self._gauge()
            if not parked:
                if self._poll is None:
                    self._poll = (state, since_ns)
                await asyncio.sleep(0.001)  # pages freeing, rows thawing: poll soon
                return 0
            if self._closed:
                return 0
            await self._flush_spans()
            self._wake.clear()
            # re-check after clear: a submit may have landed between
            # the emptiness check and the clear
            if not (self._pending or self._active):
                await self._wake.wait()
        woke_ns = time.time_ns()
        self._idle_closed("parked", since_ns, woke_ns)
        return woke_ns

    def _tell_startup(self, end_ns: int) -> None:
        """The first cycle that returned a sampled token has closed at
        ``end_ns``: close the process's start-up record (``obs/startup.py``)
        and hand it on once: the seconds a phase name on the gauge, and the
        phases as trace ``startup-<worker_id>``, children first and the root
        last, to go out with the next flush, behind a step."""
        self._startup_told = True
        # from here on the collector's pauses are read (the process's one
        # ``gc.callbacks`` entry, shared with a ``RuntimeProfiler`` beside us)
        GC_PAUSES.hold(self)
        self._gc_seen = GC_PAUSES.count
        record = startup.close(end_ns, worker_id=self.worker_id)
        if not record:
            return
        if self.metrics is not None:
            seconds: dict[str, float] = {}
            for ph in record:
                seconds[ph.name] = seconds.get(ph.name, 0.0) + ph.seconds
            for name, secs in seconds.items():
                self.metrics.startup_phase.set(secs, phase=name)
        tr = self.tracer
        if tr is None or not tr.listening():
            return
        ids = {ph.id: fast_id() for ph in record}
        for ph in record:
            self._spans.append(tr.record(
                ph.name, trace_id=f"startup-{self.worker_id}", span_id=ids[ph.id],
                parent_span_id=ids.get(ph.parent, ""),
                start_us=ph.start_ns // 1000, end_us=ph.end_ns // 1000,
                attrs={k: str(v) for k, v in ph.attrs.items()},
            ))

    async def _flush_spans(self) -> None:
        """Publish what was recorded since the last flush.  Called only
        with a step on the device (after that step's turn to tell the last
        one's tokens: ``_publish_unsent``), before the loop parks, and from
        ``stop()``."""
        if not self._spans or self.tracer is None:
            return
        spans, self._spans = self._spans, []
        for sp in spans:
            await self.tracer.emit(sp)

    async def _emit(
        self, sess: _Session, new_tokens: list[int], n_generated: int, done: bool,
        behind_step: bool = False,
    ) -> None:
        """One stream packet to the session's sink: ``new_tokens`` end at
        ``n_generated`` of its output.  The session's first closes
        ``serving.first_packet``: the first token's way out, from the stamp
        its step's bookkeeping took (``_first_token``) to the sink's return."""
        if sess.on_tokens is None:
            return
        try:
            await sess.on_tokens(new_tokens, n_generated, done)
        except Exception as e:  # noqa: BLE001 - streaming is best-effort
            logx.warn("token stream sink failed", job_id=sess.job_id, err=str(e))
        if sess.first_token_at:
            sampled_at, sess.first_token_at = sess.first_token_at, 0.0
            self._ttft_span("serving.first_packet", sess, sampled_at, time.monotonic(), {
                "behind_step": str(behind_step).lower(),
                "tokens": str(len(new_tokens)),
            })

    async def _publish_unsent(self, behind_step: bool) -> None:
        """Tell what the last step's bookkeeping (``_scatter``) left untold:
        its stream packets, in row order, then the futures of the sessions
        it retired: a future never resolves before its session's last
        packet went out.  ``behind_step``: the next step is on the device,
        so none of this holds it up; the loop calls it with False only when
        there is no next step to hand over (drained, or every row parked),
        ``stop()`` before it evicts."""
        async with self._publishing:
            while self._unsent:
                packets, self._unsent = self._unsent, []
                # each sink runs to its first real suspension in row order;
                # a bus that delivers at publish never suspends one
                await eager_gather([self._emit(*pkt, behind_step) for pkt in packets])
                for sess, _, _, _ in packets:
                    sess.unsent -= 1
                n = len(packets)
                self.stats.stream_packets += n
                self.stats.stream_packets_behind_step += n if behind_step else 0
                if self.metrics is not None:
                    self.metrics.serving_stream_packets.inc(
                        float(n), behind_step=str(behind_step).lower())
            resolved, self._unresolved = self._unresolved, []
            for sess, error in resolved:
                self._resolve(sess, error)

    def _retire(self, sess: _Session, error: Optional[BaseException] = None) -> None:
        """Take ``sess`` out of the engine: everything the next ``_admit``
        and ``_assemble`` must see happens here and now; its future
        resolves now too unless a stream packet of it is still untold, in
        which case ``_publish_unsent`` resolves it behind that packet."""
        if (
            error is None and self.prefix is not None
            and not self._closed and not sess.cancelled and sess.pages
        ):
            # retain the finished conversation's full pages under their
            # token path: the next turn (same history + new suffix) maps
            # them instead of re-prefilling.  Register BEFORE the
            # allocator drops the session's references, so a shared page
            # never transits the free list (the retain/release ordering
            # the property suite pins down).  Positions [0, pos) were
            # written; their tokens are prompt + generated output minus
            # the never-fed final sample.
            covered = (sess.req.prompt + sess.out_tokens)[:sess.pos]
            self.prefix.register(covered, sess.pages)
            if self.tiering is not None and sess.req.session_key:
                self.tiering.note_turn(sess.req.session_key, covered)
        self.allocator.free(sess.job_id)
        if self.window_allocator is not None:
            self.window_allocator.free(sess.job_id)
        if self.state_allocator is not None:
            self.state_allocator.free(sess.job_id)
        self._active.pop(sess.job_id, None)
        if error is None:
            self.stats.retired += 1
            if self.metrics is not None:
                self.metrics.serving_retired.inc(reason="finished")
        else:
            if isinstance(error, SessionCancelled):
                reason = "cancelled"
                self.stats.cancelled += 1
            elif isinstance(error, SessionMigrated):
                reason = "migrated"
                self.stats.migrated_out += 1
            elif isinstance(error, SessionHibernated):
                reason = "hibernated"
                self.stats.hibernated_out += 1
            elif isinstance(error, SessionRequeued):
                reason = "requeued"
                self.stats.requeued += 1
            else:
                reason = "failed"
            if self.metrics is not None:
                self.metrics.serving_retired.inc(reason=reason)
        if sess.unsent:
            self._unresolved.append((sess, error))
        else:
            self._resolve(sess, error)

    @staticmethod
    def _resolve(sess: _Session, error: Optional[BaseException]) -> None:
        if sess.future.done():
            return
        if error is None:
            sess.future.set_result(list(sess.out_tokens))
        else:
            sess.future.set_exception(error)

    # ------------------------------------------------------------------
    @staticmethod
    def _ngram_draft(history: list[int], k: int) -> list[int]:
        """Prompt-lookup drafting — the zero-extra-weights self-speculative
        drafter: find the most recent earlier occurrence of the history's
        final n-gram and propose the tokens that followed it.  Longest gram
        first (a longer match is stronger evidence the continuation
        repeats), most-recent-first within a gram so loops and templates
        match their latest iteration.  Returns ``[]`` when nothing matches:
        the session decodes a plain single-token row this step."""
        n = len(history)
        for g in (3, 2, 1):
            if n <= g:
                continue
            tail = history[-g:]
            # bounded lookback keeps a very long conversation O(window)
            lo = max(0, n - g - 512)
            for i in range(n - g - 1, lo - 1, -1):
                if history[i:i + g] == tail:
                    cont = history[i + g:i + g + k]
                    if cont:
                        return cont
        return []

    def _plan_drafts(self) -> None:
        """Propose draft continuations for every decoding session — BEFORE
        CoW resolution (the write span must cover the planned draft
        positions) and before assembly (which trims plans to the step's
        flat-buffer budget).  The per-session acceptance EWMA throttles the
        proposal length: a session whose drafts keep verifying ramps to
        ``draft_k``, one whose drafts keep rejecting decays to single-token
        probes.  The length clamp ``k <= remaining - 1`` guarantees a fully
        accepted burst (k drafts + the bonus token) never overshoots
        ``max_new_tokens`` — and therefore never writes outside the
        session's admitted page footprint."""
        if not self.speculative:
            return
        for sess in self._active.values():
            sess.draft_plan = []
            if not sess.prefilled or sess.frozen or sess.cancelled:
                continue
            room = sess.req.max_new_tokens - len(sess.out_tokens)
            k_cap = min(self.draft_k, room - 1)
            if k_cap < 1:
                continue
            k = 1 + int(round(sess.accept_ewma * (k_cap - 1)))
            history = sess.req.prompt + sess.out_tokens
            try:
                plan = self._drafter(history, k)
            except Exception as e:  # noqa: BLE001 - drafting is best-effort
                logx.warn("drafter failed", job_id=sess.job_id, err=str(e))
                plan = []
            sess.draft_plan = [int(t) for t in plan[:k]]

    # ------------------------------------------------------------------
    async def _resolve_cow(self) -> frozenset[str]:
        """Copy-on-write guard (docs/SERVING.md §Prefix cache and
        tiering): before assembling a step, any page a session is about
        to WRITE that another table — or the prefix cache — still
        references is duplicated onto a fresh page and swapped into this
        session's table only.  Full-page-only caching makes the trigger
        rare (a prefix hit ending exactly at the prompt end re-feeds one
        token into shared territory), but the guard is what makes sharing
        safe by construction instead of by keying convention.  Returns
        job ids that must sit this step out (no fresh page even after
        dropping the cache's own reference)."""
        skip: set[str] = set()
        ps = self.allocator.page_size
        for sess in list(self._active.values()):
            if sess.frozen or sess.cancelled or sess.job_id not in self._active:
                continue
            if sess.prefilled:
                # a draft row writes positions [pos, pos + k]: the span may
                # cross into the next page (or start inside a shared prefix
                # page), so every page it touches gets the CoW guard
                hi = sess.pos + len(sess.draft_plan)
                write_pages = range(sess.pos // ps, hi // ps + 1)
            else:
                lo = sess.prefill_pos // ps
                hi = min(
                    len(sess.prefill_seq) - 1,
                    sess.prefill_pos + self.step_tokens - 1,
                ) // ps
                write_pages = range(lo, hi + 1)
            for idx in write_pages:
                if idx >= len(sess.pages):
                    break
                if self.allocator.refcount(sess.pages[idx]) <= 1:
                    continue
                if not await self._cow(sess, idx):
                    skip.add(sess.job_id)
                    break
        return frozenset(skip)

    async def _cow(self, sess: _Session, idx: int) -> bool:
        """Give ``sess`` a private copy of page-table slot ``idx``.
        Cheapest first: under exhaustion (or when the cache is the only
        other holder left) dropping the cache's reference may already
        make this session the sole owner — no copy, no fresh page."""
        old = sess.pages[idx]
        if self.allocator.free_pages < 1 and self.prefix is not None:
            self.prefix.drop_subtree(old)
            if self.allocator.refcount(old) <= 1:
                return True
        try:
            (fresh,) = self.allocator.alloc_raw(1)
        except CacheExhausted:
            if self.prefix is not None:
                self.prefix.drop_subtree(old)
                if self.allocator.refcount(old) <= 1:
                    return True
            return False
        await self.run_blocking(self.backend.copy_page, old, fresh)
        if sess.cancelled or sess.job_id not in self._active:
            self.allocator.release([fresh])  # retired during the copy
            return True
        self.allocator.swap_owned(sess.job_id, old, fresh)
        sess.pages[idx] = fresh
        self.allocator.release([old])
        self.stats.cow_copies += 1
        if self.metrics is not None:
            self.metrics.serving_cow_copies.inc()
        return True

    # ------------------------------------------------------------------
    def _assemble(
        self, skip: frozenset = frozenset()
    ) -> tuple[list[StepEntry], list[tuple[_Session, int, bool, list[int]]]]:
        """Build one mixed step: a decode row for every prefilled session
        (with its planned draft tokens appended while the budget lasts),
        then prompt chunks for prefilling ones (admission order) within the
        flat token budget and the per-step chunk cap.  Returns the entries
        plus aligned ``(session, chunk_len, samples, draft_tokens)``
        bookkeeping.  ``skip`` rows sit this step out (CoW starved for a
        fresh page)."""
        entries: list[StepEntry] = []
        rows: list[tuple[_Session, int, bool, list[int]]] = []
        budget = self.step_tokens
        chunks = 0
        decoding = [
            # frozen = mid-migration freeze-and-delta: the session's pages
            # are being shipped; its rows sit this step (and the next) out
            s for s in self._active.values()
            if s.prefilled and not s.frozen and s.job_id not in skip
        ]
        # draft budget: the flat-buffer slots left after every decode row's
        # base token.  While prompts are waiting to prefill, drafts take at
        # most half the leftover so speculation can never starve admission
        # latency — the prefill chunks below ride the rest.
        waiting = any(
            not s.prefilled and not s.frozen and s.job_id not in skip
            for s in self._active.values()
        )
        spare = budget - len(decoding)
        draft_budget = (
            (spare // 2 if waiting else spare) if self.speculative else 0
        )
        for sess in decoding:
            plan = sess.draft_plan[:draft_budget] if draft_budget > 0 else []
            sess.draft_plan = []
            entries.append(StepEntry(
                tokens=[sess.last_token, *plan], start=sess.pos,
                pages=sess.pages, sample=True, phase="decode",
                key=sess.job_id, draft=len(plan),
                window_pages=sess.window_pages, state_slot=sess.state_slot,
            ))
            rows.append((sess, 1 + len(plan), True, plan))
            budget -= 1 + len(plan)
            draft_budget -= len(plan)
        # prefill candidates ride interactive-first (stable within a class,
        # so admission order still breaks ties): under load the leftover
        # token budget goes to interactive prompts and BATCH prefill waits —
        # batch decode rows above keep their single-token slots, only new
        # batch prompt ingestion is deprioritized (docs/ADMISSION.md)
        prefilling = [
            s for s in self._active.values()
            if not s.prefilled and not s.frozen and s.job_id not in skip
        ]
        prefilling.sort(
            key=lambda s: 0 if s.req.job_class in INTERACTIVE_CLASSES else 1
        )
        for sess in prefilling:
            if budget <= 0 or chunks >= self.max_concurrent_prefills:
                break
            # the prefill sequence is prompt + any forced-decode resume
            # prefix (minus its last token, which decodes as a normal row);
            # the completing chunk samples only for resume-free sessions
            # with output still to generate
            seq = sess.prefill_seq
            chunk = min(budget, len(seq) - sess.prefill_pos)
            completes = sess.prefill_pos + chunk >= len(seq)
            samples = (
                completes and not sess.done and not sess.req.resume_tokens
            )
            entries.append(StepEntry(
                tokens=seq[sess.prefill_pos:sess.prefill_pos + chunk],
                start=sess.prefill_pos, pages=sess.pages,
                sample=samples, phase="prefill",
                key=sess.job_id, window_pages=sess.window_pages,
                state_slot=sess.state_slot,
            ))
            rows.append((sess, chunk, samples, []))
            budget -= chunk
            chunks += 1
        return entries, rows

    async def _decode_loop(self) -> None:
        """The continuous-batching loop: one ragged XLA call per step over
        every active session — decode rows and prefill chunks mixed;
        admission and retirement happen between steps, never inside one.

        One cycle is six contiguous phases (``backend.STEP_PHASES``):
        ``assemble`` here, ``pack``/``dispatch``/``wait``/``unpack`` inside
        ``backend.step``, ``emit`` here again.  ``emit`` is bookkeeping
        only (``_scatter``): what a step's tokens make of the sessions and
        the allocators, which the next ``assemble`` reads.  Telling anybody
        (the riders' stream packets, the finishers' futures) is nothing the
        next step waits for, so it happens after that step's hand-over,
        while the device runs it (``_publish_unsent``), and before the loop
        parks where there is no next step."""
        loop = asyncio.get_running_loop()
        self._tell_fed = lambda: loop.call_soon_threadsafe(self._fed.set)
        self.backend.on_dispatched = self._tell_fed
        edge_ns = 0  # where the interval before ended: a cycle's END stamp, a park's wake
        while not self._closed:
            n_step = self.stats.steps
            # a cycle starts where the one before it ended (or the park before
            # it woke): closing a cycle's spans is the next one's ``assemble``,
            # and cycles, parks and polls cover the loop's life
            marks = [edge_ns or time.time_ns()]
            entries: list[StepEntry] = []
            rows: list[tuple[_Session, int, bool, list[int]]] = []
            with step_phase("assemble", n_step, marks):
                await self._admit()
                # evict cancellations before assembling the batch
                for sess in [s for s in self._active.values() if s.cancelled]:
                    self._retire(sess, error=SessionCancelled(sess.job_id))
                if self._active:
                    self._plan_drafts()
                    entries, rows = self._assemble(await self._resolve_cow())
                if entries:
                    t0 = time.monotonic()
                    self._in_step = frozenset(s.job_id for s, _, _, _ in rows)
                    # handed to the executor before anything is published:
                    # the publish and the flush below run while the device does
                    self._fed.clear()
                    done, step_call = eager(self._run_step(entries))
            if not entries:
                # nothing to feed: the last step's tokens and finishers now,
                # then a park or a poll
                edge_ns = await self._unfed(marks[0])
                continue
            in_backend = not done
            if in_backend:
                # the executor thread packs and dispatches in Python: what
                # follows would hold the interpreter against it, and the
                # program would start late
                await self._fed.wait()
            if self._poll is not None:  # it ends where this cycle began
                self._idle_closed(*self._poll, marks[0])
                self._poll = None
            # the last step's tokens and finishers, behind this step
            await self._publish_unsent(behind_step=in_backend)
            # yield once: the deliveries the publish queued, and intake,
            # cancel and heartbeat tasks, run even under a saturated decode
            # set (and when the step came back at once)
            await asyncio.sleep(0)
            await self._flush_spans()
            results, step_err = step_call if done else await step_call
            if step_err is not None:
                # a poisoned step fails every rider (pages freed); the next
                # tick starts clean — mirrors the batcher's isolation intent
                # without re-running autoregressive state per item
                self._in_step = frozenset()
                logx.warn("serving step failed", occupancy=len(rows),
                          err=str(step_err))
                if self.tracer is not None and self.tracer.listening():
                    self._spans.append(self.tracer.record(
                        "step", trace_id=f"step-{self.worker_id}-{n_step}",
                        start_us=marks[0] // 1000,
                        end_us=time.time_ns() // 1000, status=SPAN_ERROR,
                        attrs={"occupancy": str(len(rows)),
                               "error": type(step_err).__name__},
                    ))
                for sess, _, _, _ in rows:
                    self.stats.failed += 1
                    self._retire(sess, error=step_err)
                edge_ns = 0
                continue
            marks.append(time.time_ns())
            dt = time.monotonic() - t0
            with step_phase("emit", n_step, marks):
                attrs = self._scatter(rows, results, dt)
                self._gauge()
            edge_ns = marks[-1]
            # by its stamps what follows is already the next cycle's ``assemble``
            with annotation("cordum.step.assemble", step=n_step + 1):
                self._cycle_closed(n_step, marks, attrs)
                if not self._startup_told and any(r is not None for r in results):
                    self._tell_startup(marks[-1])

    async def _run_step(
        self, entries: list[StepEntry]
    ) -> tuple[list[Any], Optional[Exception]]:
        """``backend.step`` off the loop; a whole-step failure comes back as
        a value, so the hand-over and the await share one error path."""
        try:
            return await self.run_blocking(self._step_in_thread, entries), None
        except Exception as e:  # noqa: BLE001 - whole-step failure
            return [], e

    def _step_in_thread(self, entries: list[StepEntry]) -> list[Any]:
        """``backend.step`` (whatever stands there when the step runs), on
        the executor thread.  A step that ends without having said it was
        fed (it raised first, or its backend says nothing) says so here:
        the loop waits for that word before it awaits the step itself."""
        try:
            return self.backend.step(entries)
        finally:
            if not self._fed.is_set():
                self._tell_fed()

    def _scatter(
        self,
        rows: list[tuple[_Session, int, bool, list[int]]],
        results: list[Any],
        dt: float,
    ) -> dict[str, str]:
        """Scatter one step's results back to its riders and retire the
        finishers: bookkeeping alone, nothing here awaits.  A rider's new
        tokens go on ``_unsent`` as one stream packet, stamped with the
        count and the ``done`` they were booked at; a finisher's pages are
        free when this returns, its future waits for its packet
        (``_retire``).  ``dt`` is the whole ``backend.step`` wall.  Returns
        the cycle's ``step`` span attrs."""
        generated = 0
        prefill_fed = 0
        retired_this_step = 0
        step_drafted = 0
        step_accepted = 0
        pos_before = [sess.pos for sess, _, _, _ in rows] if self.ring_pages else []
        for (sess, chunk, samples, drafted), tok in zip(rows, results):
            if sess.ttft is not None:
                sess.ttft.steps += 1
                if not sess.prefilled:
                    sess.ttft.chunks += 1
            if drafted:
                # speculative verification row: the backend returned
                # one next-token prediction per fed position.  Accept
                # the longest draft prefix the model agrees with, then
                # the bonus token — the prediction after the last
                # accepted draft, which is exactly what a sequential
                # decode would have sampled next (so the burst is
                # token-identical to the oracle by construction).
                preds = [int(t) for t in tok]
                a = 0
                while a < len(drafted) and drafted[a] == preds[a]:
                    a += 1
                burst = drafted[:a] + [preds[a]]
                eos = sess.req.eos_token
                if eos is not None and eos in burst:
                    burst = burst[:burst.index(eos) + 1]
                rejected = len(drafted) - a
                step_drafted += len(drafted)
                step_accepted += a
                frac = a / len(drafted)
                sess.accept_ewma += SPEC_EWMA_ALPHA * (
                    frac - sess.accept_ewma
                )
                self.spec_accept_ewma += SPEC_FLEET_ALPHA * (
                    frac - self.spec_accept_ewma
                )
                self.stats.drafted_tokens += len(drafted)
                self.stats.accepted_tokens += a
                self.stats.rolled_back_tokens += rejected
                if self.metrics is not None:
                    self.metrics.serving_spec_drafted.inc(
                        float(len(drafted)))
                    self.metrics.serving_spec_accepted.inc(float(a))
                    if rejected:
                        self.metrics.serving_spec_rolled_back.inc(
                            float(rejected))
                # page write-position rollback: pos advances over the
                # verified burst ONLY.  Rejected draft positions sit at
                # >= the new pos; every later step writes its own K/V
                # there before any gather runs (writes precede gathers
                # inside the ragged program, and positions are consumed
                # contiguously), so the arena never serves speculated
                # garbage.
                first = not sess.out_tokens
                sess.pos += len(burst)
                sess.last_token = burst[-1]
                sess.out_tokens.extend(burst)
                generated += len(burst)
                if first:
                    self._first_token(sess)
                self._book_packet(sess, burst)
            else:
                if sess.prefilled:
                    sess.pos += 1  # decode row: wrote its token at pos
                else:
                    sess.prefill_pos += chunk
                    sess.pos = sess.prefill_pos
                    prefill_fed += chunk
                    self.stats.prefill_chunks += 1
                if samples and tok is not None:
                    t = int(tok)
                    sess.last_token = t
                    sess.out_tokens.append(t)
                    generated += 1
                    if len(sess.out_tokens) == 1:
                        # first token of a locally born session: TTFT
                        # (resume prefixes pre-populate out_tokens, so
                        # migrated/resumed sessions never land here)
                        self._first_token(sess)
                    self._book_packet(sess, [t])
            if sess.done or sess.cancelled:
                retired_this_step += 1
                # the future must not resolve before the session's final
                # token packet is delivered, or a submitter that stops the
                # engine the moment submit() returns races the stream's
                # tail (the exactly-once contract spec bursts lean on):
                # _retire leaves it to the publish of that packet
                self._retire(
                    sess,
                    error=SessionCancelled(sess.job_id)
                    if sess.cancelled else None,
                )
            elif (
                self.on_prefill_done is not None
                and not sess.handoff_signaled
                and not sess.frozen
                and (sess.prefilled or (
                    self.handoff_threshold_tokens > 0
                    and sess.prefill_pos >= self.handoff_threshold_tokens
                ))
            ):
                # post-prefill hand-off trigger: the prompt finished
                # prefilling (or crossed the threshold mid-prefill) and
                # the session still has tokens to generate — the hook
                # fires once; the owner decides whether/where to migrate
                sess.handoff_signaled = True
                try:
                    self.on_prefill_done(sess.job_id)
                except Exception as e:  # noqa: BLE001 - policy is best-effort
                    logx.warn("prefill-done hook failed",
                              job_id=sess.job_id, err=str(e))
        self.stats.steps += 1
        self.stats.decoded_tokens += generated
        self.stats.prefill_tokens += prefill_fed
        if step_drafted:
            self.stats.spec_steps += 1
        self.stats.occupancy_sum += len(rows)
        self.stats.max_occupancy = max(self.stats.max_occupancy, len(rows))
        self.stats.step_seconds.append(dt)
        if self.capacity is not None:
            # one mixed step at the backend's static flat-buffer shape;
            # warmup compiles are flagged so the steady-state tokens/s
            # rows in the capacity matrix exclude them.  The step's
            # device time is apportioned by delivered tokens between
            # prompt ingestion (the OP_SERVING_PREFILL row) and token
            # generation (the llm.generate row), so prefill tokens/s
            # and decode tokens/s are separately measurable — the
            # disaggregation policy's two placement signals
            # (docs/SERVING.md §Disaggregation)
            compiled = self.backend.last_step_compiled
            total_toks = generated + prefill_fed
            if prefill_fed:
                self.capacity.observe(
                    OP_SERVING_PREFILL,
                    device_s=dt * prefill_fed / total_toks,
                    bucket=str(self.step_tokens),
                    items=prefill_fed, tokens=prefill_fed,
                    compiled=compiled,
                )
            if generated or not prefill_fed:
                self.capacity.observe(
                    "llm.generate",
                    device_s=(dt * generated / total_toks
                              if total_toks else dt),
                    bucket=str(self.step_tokens),
                    items=generated, tokens=generated,
                    compiled=compiled,
                )
        # every token of this step is appended; a freeze waiting on
        # wait_quiesced() also waits until the session's packet is out
        self._in_step = frozenset()
        if self.metrics is not None:
            self.metrics.serving_batch_occupancy.observe(float(len(rows)))
            self.metrics.serving_inter_token.observe(dt)
        walked, of = self.backend.last_attn_blocks
        self.stats.attn_blocks_walked += walked
        self.stats.attn_blocks_total += of
        kv_rows, q_rows = self.backend.last_attn_rows
        self.stats.attn_rows_gathered += kv_rows
        self.stats.attn_slots_computed += q_rows
        self.stats.attn_slots_live += self.backend.last_attn_live
        self.stats.kv_bytes_behind_rows += self.backend.page_bytes * sum(
            self.allocator.pages_for(sess.pos) for sess, _, _, _ in rows)
        attrs = {
            "occupancy": str(len(rows)),
            "live_tokens": str(sum(chunk for _, chunk, _, _ in rows)),
            "prefill_tokens": str(prefill_fed),
            "retired": str(retired_this_step),
            "compiled": str(self.backend.last_step_compiled).lower(),
        }
        if self.backend.last_step_compiled:
            attrs["compile_ms"] = f"{self.backend.last_compile_ms:.3f}"
            attrs["cache_hit"] = str(self.backend.last_cache_hit).lower()
        if of:
            attrs["kv_blocks"] = f"{walked}/{of}"
            attrs["kv_block_tokens"] = str(self.backend.attn_block_tokens)
            attrs["kv_rows"] = str(kv_rows)
            attrs["q_rows"] = str(q_rows)
            attrs["q_live"] = str(self.backend.last_attn_live)
            attrs["walk_kernel"] = self.backend.kernels.get("walk") or "none"
        if self.ring_pages:
            attrs["window_blocks"] = str(self._count_window(rows, pos_before))
            attrs["ring_kernel"] = self.backend.kernels.get("ring") or "none"
        if self.state_allocator is not None:
            # every fed row advanced its state slot: by one token (a state
            # read and written for it) or by a chunk
            single = sum(1 for _, chunk, _, _ in rows if chunk == 1)
            fed = sum(chunk for _, chunk, _, _ in rows)
            self.stats.state_decode_rows += single
            self.stats.state_chunk_tokens += fed - single
            attrs["state_rows"] = str(len(rows))
            attrs["state_tokens"] = str(fed)
        # what the model family's program counted this step and what it says
        # of that on the span, both under the family's own names
        # (``ModelSpec.count_aux``)
        self.stats.model.update(self.backend.last_counters)
        attrs.update(self.backend.last_attrs)
        if self.speculative:
            attrs["drafted"] = str(step_drafted)
            attrs["accepted"] = str(step_accepted)
        # of the stream packets published since the last cycle closed (the
        # step before this one's), those that went out behind this step
        behind, told = self.stats.stream_packets_behind_step, self.stats.stream_packets
        behind0, told0 = self._packets_at_close
        attrs["published_behind"] = f"{behind - behind0}/{told - told0}"
        self._packets_at_close = (behind, told)
        return attrs

    def _book_packet(self, sess: _Session, new_tokens: list[int]) -> None:
        """One step's new tokens of ``sess`` as a stream packet to tell
        later (``_publish_unsent``), as the session stands now."""
        if sess.on_tokens is None:
            return
        sess.unsent += 1
        self._unsent.append((sess, new_tokens, len(sess.out_tokens), sess.done))

    def _count_window(
        self, rows: list[tuple[_Session, int, bool, list[int]]], pos_before: list[int],
    ) -> int:
        """One step's part of the window counters of ``ServingStats`` (a
        model with window layers); returns the blocks its window layers'
        walk read."""
        st = self.stats
        ps, ring = self.allocator.page_size, self.ring_pages
        for (sess, _, _, _), before in zip(rows, pos_before):
            # logical pages first written this step, of those past the ring
            new = -(-sess.pos // ps) - max(-(-before // ps), ring)
            if new > 0:
                st.window_pages_reused += new
        blocks = self.backend.last_window_blocks
        st.window_blocks_walked += blocks
        return blocks

    # ------------------------------------------------------------------
    # live migration (serving/migration.py, docs/SERVING.md §Migration,
    # drain, and failover).  The engine side is deliberately mechanical:
    # describe → stream stable pages live → freeze → export the delta →
    # complete (retire as SessionMigrated) or unfreeze on failure.
    # ------------------------------------------------------------------
    def session_ids(self) -> list[str]:
        """Every live session, decoding first (pending last): the order a
        drain migrates them in — decoding sessions carry KV state worth
        moving; pending ones are requeued cheaply."""
        return [*self._active.keys(), *(s.job_id for s in self._pending)]

    def pick_rebalance_sessions(self, n: int = 1) -> list[str]:
        """Cheapest movable sessions for a governor rebalance
        (docs/SERVING.md §Disaggregation): active, unfrozen, uncancelled,
        and past their migrated-in cooldown — a session the governor (or a
        hand-off) just placed here is immune, so skew oscillation can
        never ping-pong it.  Cheapest = fewest live pages, then oldest
        (smallest) position — the least KV state to ship; sessions still
        prefilling qualify (they are the cheapest of all, and migration
        resumes prefill on the target).  Drain uses :meth:`session_ids`
        instead and ignores immunity (a draining worker must move
        everything)."""
        if not self.kv_portable:
            return []  # its pages cannot be shipped: nothing is movable
        now = time.monotonic()
        cands = [
            s for s in self._active.values()
            if not s.frozen and not s.cancelled
            and not s.done and s.immune_until <= now
        ]
        cands.sort(key=lambda s: (len(s.pages), s.pos))
        return [s.job_id for s in cands[:max(0, n)]]

    def describe_session(self, job_id: str) -> Optional[dict[str, Any]]:
        """The session's immutable metadata (the migration hello frame);
        None when it is not actively decoding here."""
        sess = self._active.get(job_id)
        if sess is None or sess.cancelled or not self.kv_portable:
            # a model with window layers or latent pages is never offered for migration:
            # the drain falls back to a scheduler requeue (re-prefill)
            return None
        req = sess.req
        return {
            "job_id": sess.job_id,
            "prompt": list(req.prompt),
            "resume_tokens": list(req.resume_tokens),
            "max_new_tokens": req.max_new_tokens,
            "session_key": req.session_key,
            "eos_token": req.eos_token,
            "stream": req.stream,
            "trace_id": sess.trace_id,
            "page_size": self.allocator.page_size,
            "n_pages": len(sess.pages),
        }

    def export_state(self, job_id: str) -> Optional[dict[str, Any]]:
        """The session's mutable decode state — valid only once frozen and
        quiesced (the commit frame's ``state``)."""
        sess = self._active.get(job_id)
        if sess is None:
            return None
        return {
            "pos": sess.pos,
            "prefill_pos": sess.prefill_pos,
            "out_tokens": list(sess.out_tokens),
            "last_token": sess.last_token,
        }

    async def export_pages(
        self, job_id: str, start_tok: int, end_tok: int
    ) -> list[dict]:
        """Page records covering positions ``[start_tok, end_tok)`` at
        their true lengths."""
        self._require_records("page export (migration, hibernation)")
        sess = self._active.get(job_id)
        if sess is None:
            return []
        return await self.run_blocking(
            self.backend.export_kv, sess.pages, start_tok, end_tok)

    def freeze_session(self, job_id: str) -> bool:
        """Pause the session's decode (it sits out subsequent steps);
        False when it is not actively decoding here."""
        sess = self._active.get(job_id)
        if sess is None or sess.cancelled:
            return False
        sess.frozen = True
        return True

    def unfreeze_session(self, job_id: str) -> None:
        """Resume a frozen session (migration failed: decode continues
        locally as if nothing happened)."""
        sess = self._active.get(job_id)
        if sess is not None:
            sess.frozen = False
            self._wake.set()

    async def wait_quiesced(self, job_id: str) -> None:
        """Block until the in-flight step (which may still produce one
        token for a just-frozen session) has scattered its results AND
        every token appended to the session has been published (a step's
        packets go out behind the next hand-over, whether or not the
        session rides that step)."""
        while job_id in self._in_step or self._untold(job_id):
            await asyncio.sleep(0.002)

    def _untold(self, job_id: str) -> bool:
        sess = self._active.get(job_id)
        return sess is not None and sess.unsent > 0

    def complete_migration(self, job_id: str) -> bool:
        """The target committed: retire locally as migrated — the waiter
        publishes nothing (the target owns stream + terminal result)."""
        sess = self._active.get(job_id)
        if sess is None:
            return False
        self._retire(sess, error=SessionMigrated(job_id))
        return True

    async def hibernate_session(self, job_id: str) -> bool:
        """Freeze a live session and tier it whole into the host-RAM cold
        arena — the local analogue of live migration (same record format,
        no peer): freeze → quiesce → export state + pages → retire
        ``reason="hibernated"``.  The submit waiter gets
        :class:`SessionHibernated` and publishes nothing;
        :meth:`restore_hibernated` later owns the token stream and the
        terminal result.  False when the session is not live here (or
        tiering is disabled)."""
        self._require_records("hibernation")
        if self.tiering is None:
            return False
        meta = self.describe_session(job_id)
        if meta is None or not self.freeze_session(job_id):
            return False
        try:
            await self.wait_quiesced(job_id)
            state = self.export_state(job_id)
            if state is None:
                return False
            records = await self.export_pages(job_id, 0, int(state["pos"]))
        except BaseException:
            self.unfreeze_session(job_id)
            raise
        sess = self._active.get(job_id)
        if sess is None or sess.cancelled:
            self.unfreeze_session(job_id)
            return False
        self.tiering.arena.put(job_id, {
            "meta": meta, "state": state, "records": records,
        })
        self._retire(sess, error=SessionHibernated(job_id))
        if self.metrics is not None:
            self.metrics.serving_hibernate.inc(event="hibernated")
        return True

    async def restore_hibernated(
        self,
        job_id: str,
        *,
        on_tokens: Optional[TokenSink] = None,
    ) -> asyncio.Future:
        """Re-admit a hibernated session from the cold arena via the
        existing :meth:`install_session` path; carried tokens replay at
        offset 0, so offset-deduping stream consumers see an exactly-once
        sequence across the gap.  Raises ``KeyError`` when the arena has
        no such session; on install failure (exhaustion) the cold doc is
        put back, restorable later."""
        if self.tiering is None:
            raise KeyError(job_id)
        doc = self.tiering.arena.pop(job_id)
        if doc is None:
            raise KeyError(job_id)
        meta, state = doc["meta"], doc["state"]
        eos = meta.get("eos_token")
        req = GenRequest(
            prompt=[int(t) for t in meta["prompt"]],
            max_new_tokens=int(meta["max_new_tokens"]),
            session_key=str(meta.get("session_key", "")),
            eos_token=int(eos) if isinstance(eos, int) else None,
            stream=bool(meta.get("stream", True)),
            resume_tokens=[int(t) for t in meta.get("resume_tokens") or []],
        )
        t0 = time.monotonic()
        try:
            fut = await self.install_session(
                req, job_id=job_id, state=state, records=doc["records"],
                trace_id=str(meta.get("trace_id", "")),
                on_tokens=on_tokens, origin="hibernate",
            )
        except BaseException:
            self.tiering.arena.put(job_id, doc)
            raise
        self.stats.restored_in += 1
        if self.metrics is not None:
            self.metrics.serving_hibernate.inc(event="restored")
            self.metrics.serving_hibernate_pause.observe(time.monotonic() - t0)
        return fut

    def requeue(self, job_id: str, reason: str = "") -> bool:
        """Hand a session (pending or active) back to the scheduler for
        failover — the drain fallback when no peer can take its pages."""
        for i, sess in enumerate(self._pending):
            if sess.job_id == job_id:
                del self._pending[i]
                self._retire(sess, error=SessionRequeued(reason or job_id))
                return True
        sess = self._active.get(job_id)
        if sess is None:
            return False
        self._retire(sess, error=SessionRequeued(reason or job_id))
        return True

    async def install_session(
        self,
        req: GenRequest,
        *,
        job_id: str,
        state: dict[str, Any],
        records: list[dict],
        trace_id: str = "",
        parent_span_id: str = "",
        on_tokens: Optional[TokenSink] = None,
        origin: str = "migration",
    ) -> asyncio.Future:
        """Adopt a migrated-in session: allocate fresh arena blocks,
        scatter the shipped page records into them, and resume decoding
        exactly where the source froze.  Raises (``CacheExhausted`` /
        ``ValueError``) when this worker cannot take it — the source then
        falls back to a scheduler requeue.  Returns the session's result
        future (token list).  ``origin="hibernate"`` (the
        :meth:`restore_hibernated` path) books the adoption under the
        hibernate counters instead of the migration ones."""
        self._require_records("adopting a migrated or hibernated session")
        if self._closed:
            raise RuntimeError("serving engine is stopped")
        if job_id in self._active or any(
            s.job_id == job_id for s in self._pending
        ):
            raise ValueError(f"session {job_id} already live on this worker")
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_context:
            raise ValueError(
                f"migrated session spans {total} tokens; backend max_context "
                f"is {self.max_context}"
            )
        if len(self._active) >= self.max_sessions:
            raise CacheExhausted(
                f"{len(self._active)} active sessions; max {self.max_sessions}"
            )
        pages = self.allocator.alloc(job_id, self.allocator.pages_for(total))
        try:
            if records:
                await self.run_blocking(self.backend.import_kv, pages, records)
        except BaseException:
            self.allocator.free(job_id)
            raise
        sess = _Session(
            job_id=job_id, req=req,
            future=asyncio.get_running_loop().create_future(),
            on_tokens=on_tokens if req.stream else None,
            trace_id=trace_id, parent_span_id=parent_span_id,
        )
        sess.pages = pages
        sess.pos = int(state.get("pos", 0) or 0)
        sess.prefill_pos = int(state.get("prefill_pos", 0) or 0)
        sess.out_tokens = [int(t) for t in state.get("out_tokens") or []]
        sess.last_token = int(state.get("last_token", 0) or 0)
        # anti-ping-pong cooldown: a just-adopted session may not be picked
        # for another governor rebalance until the window passes
        sess.immune_until = time.monotonic() + self.migrate_in_cooldown_s
        # a migrated-in session never re-fires the source's hand-off hook:
        # it is already where the policy put it
        sess.handoff_signaled = True
        self._active[job_id] = sess
        self.stats.admitted += 1
        if origin == "migration":
            self.stats.migrated_in += 1
        if self.metrics is not None:
            self.metrics.serving_admitted.inc()
            if origin == "migration":
                self.metrics.serving_migrations.inc(role="in", outcome="ok")
        if sess.out_tokens and sess.on_tokens is not None:
            # replay the carried tokens at offset 0: dedupe-by-offset makes
            # it a no-op for clients that saw them and a backfill for
            # clients that lost packets in the handover window
            asyncio.ensure_future(self._emit(
                sess, list(sess.out_tokens), len(sess.out_tokens), sess.done))
        if sess.done:
            self._retire(sess)
        else:
            self._ensure_loop()
            self._wake.set()
        self._gauge()
        return sess.future

    # ------------------------------------------------------------------
    # cordum: single-flight -- sole caller is the owning runner's shutdown path; the cancel/await/None teardown is idempotent
    async def stop(self) -> None:
        """Evict every session (CANCELLED) and stop the loop — worker
        shutdown; generations are conversation turns, not batch jobs, so
        draining them could take unboundedly long."""
        self._closed = True
        self._wake.set()
        if self._tiering_task is not None:
            self._tiering_task.cancel()
            try:
                await self._tiering_task
            except asyncio.CancelledError:
                pass
            except Exception as e:  # noqa: BLE001 - logged, never swallowed
                logx.warn("tiering sweep crashed during shutdown", err=str(e))
            self._tiering_task = None
        # tokens a step booked go out before their sessions' cancellations
        await self._publish_unsent(behind_step=False)
        for sess in list(self._pending):
            if not sess.future.done():
                sess.future.set_exception(SessionCancelled(sess.job_id))
        self._pending.clear()
        for sess in list(self._active.values()):
            sess.cancelled = True
            self._retire(sess, error=SessionCancelled(sess.job_id))
        if self._loop_task is not None:
            self._loop_task.cancel()
            try:
                await self._loop_task
            except asyncio.CancelledError:
                pass
            except Exception as e:  # noqa: BLE001 - logged, never swallowed
                logx.warn("decode loop crashed during shutdown", err=str(e))
            self._loop_task = None
        self.backend.on_dispatched = None  # the loop's; it spoke to this event loop
        GC_PAUSES.release(self)
        await self._flush_spans()
