"""The XLA half of the serving path: ONE ragged mixed prefill+decode entry.

One backend per worker process owns the KV-page arenas and a single jitted
program, both supplied by a model specification (``serving/modelspec.py``: a
dense family's K and V pages by head and its ragged step are the first; a
family with window layers brings a second kind of page, held in rings).  The
page programs and the rules of the attention's walk, which every family
shares, come from ``models/attention.py``: the one module of ``models/`` this
one imports.
Every device call — a decode step over the live
sessions, a chunk of some prompt's prefill, or any mix of the two — flows
through :meth:`step` with the same static operand shapes:

  * a flat token buffer of ``max_batch_tokens`` slots (decode last-tokens
    and prefill chunk tokens interleaved, tail padded onto the null page);
  * per-sequence metadata: page tables ``[max_seqs + 1, pages_per_seq]``
    (the +1 row is the all-null padding row), per-token sequence ids and
    positions, and each sequence's sampling index.

All of them travel as ONE packed int32 host vector (:class:`FeedLayout`):
one transfer in, one call, and one transfer out, started at dispatch.

Because the shapes never change, XLA compiles exactly **one** program —
there is no prompt-length bucket ladder, no pow2 batch buckets, and no
recompile cliff when sessions join or leave (the Ragged Paged Attention
argument, PAPERS.md).  What the compiler really did is measured, not
guessed: the backend holds ``obs/startup.py``'s ``backend_call`` open round
its own jitted calls, so JAX's trace, lowering and backend-compile events of
THOSE calls (and of nobody else's in the process) become the start-up
record's ``startup.program`` phases, ``cordum_serving_compile_total{entry}``
(one count a compile request) and the step's report: ``last_step_compiled``
(a compile request fell in this step's cycle: the ``step`` span's
``compiled``, ``compile_ms``, ``cache_hit``, and what lets the capacity
observatory keep warm-up compiles out of the steady-state throughput rows).
``compiled_programs()`` is only a count of the shape keys ``step`` has been
called with (one, by construction).

:meth:`step` is **blocking** (called from the worker's executor threads)
and serializes page-arena mutations under one lock: the functional
``.at[].set`` updates would silently drop each other's writes if two steps
interleaved on the same arrays.  The engine issues one step at a time, so
the lock is a safety net for the compat wrappers (:meth:`prefill` /
:meth:`decode`) that tests and benches drive directly.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from dataclasses import dataclass, field
from types import MappingProxyType
from typing import Any, Callable, Iterator, Mapping, Optional

import numpy as np

from ..obs import startup

DEFAULT_MAX_SEQS = 16

# the step cycle's six contiguous phases, in order (docs/OBSERVABILITY.md
# §Serving spans and metrics): the engine stamps ``assemble`` and ``emit``
# on the event loop, the backend the four between them on its executor
# thread
STEP_PHASES = ("assemble", "pack", "dispatch", "wait", "unpack", "emit")


def annotation(name: str, **attrs: Any) -> contextlib.AbstractContextManager:
    """The host annotation ``name`` on the profiler's host plane, for the
    ``with`` it is entered in: the event a device trace shows under the name
    of the span the engine builds from its own stamps.  Inert with no
    profiler session, and a process that never imported jax has no profiler
    to annotate for (this module stays jax-free for the fakes)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **attrs)


@contextlib.contextmanager
def step_phase(name: str, step: int, marks: list[int]) -> Iterator[None]:
    """One phase of step cycle ``step``: appends the phase's END boundary to
    ``marks`` (``time.time_ns()``, the clock of ``now_us()`` and of the
    profiler's host plane) and meanwhile holds the host annotation
    ``cordum.step.<name>`` open, so the span the engine builds from the
    stamps and the event in a device trace carry one name."""
    with annotation(f"cordum.step.{name}", step=step):
        yield
    marks.append(time.time_ns())


@dataclass
class StepEntry:
    """One sequence's contribution to a mixed ragged step.

    A decode step feeds exactly one token (the session's last emitted
    token) at its current position; a prefill chunk feeds a slice of the
    prompt starting at ``start``.  ``sample=True`` asks for the next token
    from the last fed position (always for decode; only for the chunk that
    completes a prompt).

    ``draft > 0`` marks a speculative verification row (docs/SERVING.md
    §Speculative decoding): ``tokens`` is ``[last_token, d_1..d_k]`` — the
    session's last emitted token followed by ``draft`` drafted
    continuations — and :meth:`LlamaServingBackend.step` returns the
    per-position next-token predictions for ALL k+1 fed positions (a
    ``list[int]``) instead of the single sequence-final sample.  The row
    is prefill-shaped on the wire; only the result shape differs."""

    tokens: list[int]
    start: int  # global sequence position of tokens[0]
    pages: list[int]  # the session's page list (page-table row prefix)
    sample: bool = True
    phase: str = "decode"  # "prefill" | "decode" — observability + fakes
    key: str = ""  # session/job id — observability + fakes
    draft: int = 0  # >0: speculative row with this many drafted tokens
    # the session's ring of window-layer pages (a model with window layers)
    window_pages: list[int] = field(default_factory=list)
    # the session's state slot (a model with recurrent state); 0 is the null slot
    state_slot: int = 0


class StepBackend:
    """What runs a :class:`~cordum_tpu.serving.engine.ServingEngine`'s step:
    the one contract between the engine and a backend (docs/SERVING.md
    §Backend contract).  ``ServingBackend`` (and through it
    ``ShardedServingBackend``), ``ServingGangGroup`` and the tests' fake
    inherit it; the engine reads these members and asks for nothing else.
    The values here are what a backend without the feature reports."""

    # the static shapes, fixed at construction
    SHAPES = ("num_pages", "page_size", "max_context", "max_seqs",
              "max_batch_tokens", "ring_pages", "num_window_pages", "attn_block_tokens")
    num_pages: int
    page_size: int
    max_context: int
    max_seqs: int
    max_batch_tokens: int
    ring_pages: int = 0  # a window layer's ring, pages a sequence
    num_window_pages: int = 0  # that kind's pool
    # positions a block of the attention's walk over whole rows holds: the
    # unit of ``last_attn_blocks`` (0: a backend that walks nothing)
    attn_block_tokens: int = 0
    # the one capability: every layer's pages cover the whole row under one
    # table — what prefix sharing, hibernation, migration and the gang
    # assume.  False for a model with window layers (``ModelSpec.window``).
    kv_whole_row: bool = True
    # a page is K and V records by head — what migration and hibernation
    # records and the gang's head sharding carry.  False for a latent page
    # (``ModelSpec.arenas``); such a model still shares prefixes: copying a
    # page maps over whatever arenas its kind has
    kv_by_head: bool = True
    # bytes one page of the whole-row kind holds, all its arenas and layers
    page_bytes: int = 0
    # everything the model keeps of a row lies at a position, in a page.
    # False for a model with recurrent state (``ModelSpec.init_state``): a
    # session then also holds one of ``state_slots`` slots (slot 0 is the
    # null slot of padding rows) of ``state_bytes`` each, all state layers;
    # what shares, re-feeds or carries positions refuses such a model
    kv_positional: bool = True
    state_slots: int = 0
    state_bytes: int = 0
    # which kernels the lowered step program holds, by role (``walk``: the
    # attention's walk over the whole-row kind of page; ``ring``: over the
    # window kind's rings; ``expert``; ``state``),
    # as the model's specification states them for the platform the arenas
    # live on (``ModelSpec.kernels``): a role that is absent or "" is the
    # ``jax.numpy`` form.  Known once the state is on its device
    kernels: Mapping[str, str] = MappingProxyType({})
    # the latest step's report, written by ``step`` and read by the engine
    # after the call
    REPORT = ("last_step_compiled", "last_compile_ms", "last_cache_hit", "last_phases",
              "last_ready_ns", "last_attn_blocks", "last_window_blocks", "last_attn_rows", "last_attn_live",
              "last_counters", "last_attrs")
    # did the compiler run for this backend since the step before returned
    # (in this step's dispatch, or in a page program the cycle called first:
    # a first copy-on-write)?  For how long, and did the persistent cache
    # serve every request of it?  From JAX's own events, not a guess
    last_step_compiled: bool = False
    last_compile_ms: float = 0.0
    last_cache_hit: bool = False
    # its boundaries, ns: (entry, arrays packed, program dispatched, result
    # on the host, return) — the engine splits its step cycle by them
    last_phases: tuple[int, ...] = ()
    # inside its ``wait`` (between the third and fourth boundary), ns: the
    # result is ready on the device, its way back to the host begins; None
    # from a backend that does not say
    last_ready_ns: Optional[int] = None
    # the attention walk: (blocks read, blocks a page table holds); the
    # window layers' blocks read; (tiles' table rows gathered, query slots
    # computed), blocks of them, by one full and one window layer together;
    # the query slots among those that were FED and needed their block (the
    # rest is what the tiles pad); what the family's program counted, under
    # the family's names (``ModelSpec.count_aux``: the addends of
    # ``ServingStats.model``), and the ``step`` span's attributes of them
    last_attn_blocks: tuple[int, int] = (0, 0)
    last_window_blocks: int = 0
    last_attn_rows: tuple[int, int] = (0, 0)
    last_attn_live: int = 0
    last_counters: Mapping[str, int] = MappingProxyType({})
    last_attrs: Mapping[str, str] = MappingProxyType({})
    # observation tap: called with the entry list after every successful
    # step — the serving-gang leader broadcasts it so followers replay the
    # identical program against their head shards
    on_step: Optional[Callable[[list["StepEntry"]], None]] = None
    # called once a step, from the step's own thread, the moment the step is
    # fed: its program is on the device and nothing of it needs the
    # interpreter until the result is back.  The engine starts what may
    # hold the interpreter (publishing the last step's tokens) only then.
    # A backend with no feed of its own calls it on entry
    on_dispatched: Optional[Callable[[], None]] = None

    def stamp_whole_call(self, t0: int) -> None:
        """``last_phases`` of a step with no pack, dispatch or unpack of its
        own, entered at ``t0`` (``time.time_ns()``) and returning now: five
        ordered marks that read as one ``wait``, with no word on when its
        result was ready."""
        t1 = max(t0, time.time_ns())
        self.last_phases = (t0, t0, t0, t1, t1)
        self.last_ready_ns = None

    def step(self, entries: list[StepEntry]) -> list[Any]:
        """One mixed prefill+decode call.  One value per entry, aligned:
        the next token (``int``) of a sampled entry, ``None`` for a prefill
        chunk that does not complete its prompt, one prediction per fed
        position (``list[int]``) for a draft row.  Blocking."""
        raise NotImplementedError

    def copy_page(self, src: int, dst: int) -> None:
        """Duplicate physical page ``src`` into ``dst``.  Blocking."""
        raise NotImplementedError

    def export_kv(self, pages: list[int], start_tok: int, end_tok: int) -> list[dict]:
        """Records of the pages of ``pages`` that cover positions
        ``[start_tok, end_tok)``, ``"i"`` the page's ordinal.  Blocking."""
        raise NotImplementedError

    def import_kv(self, pages: list[int], records: list[dict]) -> None:
        """Scatter exported records into ``pages``.  Blocking."""
        raise NotImplementedError


@dataclass(frozen=True)
class FeedLayout:
    """Where a step's integer operands lie in ONE int32 vector, fixed by the
    backend's static shapes: ``[tokens T | positions T | token_seq T |
    out_idx S | table of kind 0 (S+1) x P0 | table of kind 1 (S+1) x P1
    ...]``.  The host packs into views of the vector (:meth:`split` of a
    numpy array) and the jitted program takes it apart again (:meth:`split`
    of the traced operand: static slices and reshapes, free on the device),
    so both sides read the layout from here."""

    tokens: int  # T: slots of the flat token buffer
    seqs: int  # S: sequence rows (a table has one more, the padding row)
    table_widths: tuple[int, ...]  # pages a row, per kind of page
    # a model with recurrent state: one int32 a table row behind the tables,
    # the row's state slot (``S + 1``; 0 where the model keeps no state)
    state_rows: int = 0

    @property
    def size(self) -> int:
        return (3 * self.tokens + self.seqs + (self.seqs + 1) * sum(self.table_widths)
                + self.state_rows)

    def state_slot(self, feed: Any) -> Any:
        """The rows' state slots ``[S + 1]`` of ``feed``: a view, as :meth:`split`'s."""
        return feed[self.size - self.state_rows:self.size]

    def split(self, feed: Any) -> tuple[Any, Any, Any, Any, list]:
        """``(tokens, positions, token_seq, out_idx, tables)`` of ``feed``
        (int32 ``[size]``, numpy or traced): views, not copies."""
        t, rows = self.tokens, self.seqs + 1
        lo = 3 * t + self.seqs
        tables = []
        for width in self.table_widths:
            tables.append(feed[lo:lo + rows * width].reshape(rows, width))
            lo += rows * width
        return feed[:t], feed[t:2 * t], feed[2 * t:3 * t], feed[3 * t:3 * t + self.seqs], tables


def make_ragged_program(
    model: Any, layout: FeedLayout, *, sample_logits: bool, donate: bool
) -> Any:
    """The ONE jitted serving program of ``model`` (a ``ModelSpec`` or a
    family's config): ``(params, *arenas, feed) -> (out, *arenas)``.  ``feed``
    is the step's packed int32 vector; inside the jit it is taken apart by
    ``layout`` and handed to the family's ``ragged_program`` as the operands
    that one has always taken, ``(params, *arenas, tokens, positions, *page
    tables, token_seq, out_idx)`` — for the llama family ``llama.ragged_step``
    over two arenas and one table.  With ``donate`` the arenas are donated,
    so the in-place page writes never copy an arena."""
    import jax

    from .modelspec import spec_for

    spec = spec_for(model)
    family = spec.program(sample_logits)

    # the name the device trace and chip_smoke's compile log are searched for
    def ragged_program(params, *arenas_feed):
        *arenas, feed = arenas_feed
        tokens, positions, token_seq, out_idx, tables = layout.split(feed)
        if layout.state_rows:  # the state arrays ride behind the page arenas
            tables = [*tables, layout.state_slot(feed)]
        return family(params, *arenas, tokens, positions, *tables, token_seq, out_idx)

    return jax.jit(
        ragged_program,
        donate_argnums=tuple(range(1, 1 + spec.n_arenas + spec.n_state)) if donate else (),
    )


class ServingBackend(StepBackend):
    # sharded serving (serving/shard.py): follower ranks set this False and
    # compile a program whose lm_head is dead-code-eliminated — rank 0
    # alone pays sampling (docs/SERVING.md §Sharded serving)
    sample_output = True

    def __init__(
        self,
        cfg: Any = None,
        *,
        num_pages: int = 128,
        page_size: int = 16,
        max_context: int = 0,
        max_seqs: int = 0,
        max_batch_tokens: int = 0,
        seed: int = 0,
        params: Any = None,
        params_provider: Optional[Callable[[], Any]] = None,
        metrics: Any = None,
    ) -> None:
        # lazy model import keeps this module (and the engine importing it
        # for StepEntry) jax-free until a real backend is constructed
        from ..models import attention
        from .modelspec import spec_for

        if cfg is None:  # the default model of a backend built with none
            from ..models.llama import LlamaConfig

            cfg = LlamaConfig.tiny()
        # ``cfg``: a ModelSpec or a family's config object
        self.spec = spec_for(cfg)
        self.cfg = self.spec.cfg
        self.page_size = max(1, page_size)
        self.num_pages = max(2, num_pages)
        # static page-table width: the worst-case per-sequence footprint
        self.max_context = min(
            max_context or self.spec.max_seq_len, self.spec.max_seq_len
        )
        self.pages_per_seq = -(-self.max_context // self.page_size)
        # static ragged-step shapes: S sequence rows (+1 padding row) over a
        # T-slot flat token buffer.  T - S is the headroom prefill chunks
        # ride in when the decode set is full (the chunked-prefill budget).
        self.max_seqs = max(1, max_seqs or DEFAULT_MAX_SEQS)
        self.max_batch_tokens = max(
            self.max_seqs, max_batch_tokens or 2 * self.max_seqs
        )
        # a model with window layers holds a second kind of page: a ring of
        # ``ring_pages`` per sequence, out of a pool that holds a whole ring
        # for each of ``max_seqs`` sequences beside that kind's null page
        # (so a free sequence row always finds its ring); 0 and 0 without a
        # window
        self.window = self.spec.window
        self.kv_whole_row = self.spec.kv_whole_row
        self.kv_by_head = self.spec.kv_by_head
        # a model with recurrent state: a slot a sequence row beside the null
        # slot, so a free row always finds one (as a window's ring)
        self.kv_positional = self.spec.kv_positional
        self.state_slots = 0 if self.kv_positional else self.max_seqs + 1
        self.ring_pages = (
            attention.window_ring_pages(self.window, self.page_size, self.max_batch_tokens)
            if self.window else 0
        )
        self.num_window_pages = (
            self.max_seqs * self.ring_pages + 1 if self.window else 0
        )
        # how the step's integer operands are packed into one host vector
        self.feed_layout = FeedLayout(
            self.max_batch_tokens, self.max_seqs,
            (self.pages_per_seq, self.ring_pages) if self.window
            else (self.pages_per_seq,),
            state_rows=self.max_seqs + 1 if self.state_slots else 0,
        )
        self._seed = seed
        # the weights: ``params`` as given, else what ``params_provider``
        # returns at first use, else seeded random ones
        self._params_provider = (
            (lambda: params) if params is not None else params_provider
        )
        self._params: Any = None
        # the program's arenas in its argument order, a kind after the other
        # (``spec.arenas``): the whole-row kind's (K and V by head, or one
        # latent array), then the window kind's where there is one, then the
        # state arrays (``spec.init_state``), which have no page axis
        self._arenas: Optional[list] = None
        self._row_kind = slice(0, len(self.spec.arenas[0]))  # the whole-row kind's
        self._ragged_jit: Any = None
        self._compiled_shapes: set = set()  # the count behind compiled_programs()
        # compile requests since the last step's report: count, ns, cache hits
        self._paid = [0, 0, 0]
        self._metrics = metrics
        # ``last_attn_blocks`` / ``last_window_blocks`` / ``last_attn_rows``:
        # ``attention.paged_attention`` cuts the rows into tiles and walks each
        # group of tiles to its longest one, all of which follows from the
        # rows' buffer slots and positions, which the host knows from the
        # entries it packs (``attention.count_walk``)
        # the walk's tile follows the query heads a K/V head, as the program's
        h, kvh = self.cfg.n_heads, self.cfg.n_kv_heads
        self._tile_slots = attention.attn_tile_slots(h // kvh)
        # and its block, a kind of page, what a trip gathers beside what it
        # rewrites: the program's own rule over the shapes the spec states
        itemsize = np.dtype(self.cfg.dtype).itemsize
        self._block_tokens = tuple(
            self.page_size * attention.attn_block_pages(
                self.page_size, self.pages_per_seq,
                attention.arena_pos_bytes(kind, itemsize), h, kvh, self.spec.value_dim)
            for kind in self.spec.arenas)
        self.attn_block_tokens = self._block_tokens[0]
        self._attn_blocks_total = -(-self.pages_per_seq * self.page_size // self.attn_block_tokens)
        # the counters the program returned behind the tokens
        # (``spec.aux_shape``; None where the family returns none);
        # ``last_counters`` and ``last_attrs`` are what the family says of
        # them (``spec.count_aux``)
        self.last_aux: Any = None
        self._steps_done = 0  # numbers the host annotations
        # page-arena mutation lock: steps read-modify-write the K/V arrays
        # from executor threads
        self._dev_lock = threading.Lock()

    # ------------------------------------------------------------------
    # the whole-row kind's K and V arenas under the names they always had
    # (the gang code, chip_smoke and the llama benchmark family read and
    # assign them); a latent kind has one arena, ``_k_pages``, and no V
    @property
    def _k_pages(self) -> Any:
        return self._arenas[0] if self._arenas else None

    @_k_pages.setter
    def _k_pages(self, value: Any) -> None:
        self._set_arena(0, value)

    @property
    def _v_pages(self) -> Any:
        return self._arenas[1] if self._arenas else None

    @_v_pages.setter
    def _v_pages(self, value: Any) -> None:
        self._set_arena(1, value)

    def _set_arena(self, i: int, value: Any) -> None:
        if self._arenas is None:
            self._arenas = [None] * (self.spec.n_arenas + self.spec.n_state)
        self._arenas[i] = value

    def release_arenas(self) -> None:
        """Drop every page arena (the weights stay): for an owner that is
        done serving and wants the device memory back.  A later step would
        find no arena; build a new backend instead."""
        with self._dev_lock:
            self._arenas = [None] * (self.spec.n_arenas + self.spec.n_state)

    def _ensure(self) -> None:
        if self._params is not None:
            return
        import jax

        with startup.backend_call() as events, startup.phase("startup.state") as state:
            with startup.phase("startup.weights"):
                given = self._params_provider() if self._params_provider is not None else None
                params = jax.block_until_ready(self._make_params(given))
            with startup.phase("startup.arenas") as made:
                self._arenas = list(jax.block_until_ready(self._make_arenas()))
                made["bytes"] = sum(a.nbytes for a in self._arenas)
                if self.state_slots:
                    made["state_bytes"] = sum(
                        a.nbytes for a in self._arenas[self.spec.n_arenas:])
                    self.state_bytes = made["state_bytes"] // self.state_slots
            self._params = params
            # which kernels the lowered program will hold is the model's to
            # say (``ModelSpec.kernels``), for the platform the arenas live on
            # and the devices they are laid out over.  The kernels' modules
            # import Pallas, a second or more: stamped, so that the record
            # says so
            with startup.phase("startup.kernels") as held:
                from ..models import attention

                self.kernels = dict(self.spec.kernels(
                    next(iter(self._arenas[0].devices())).platform,
                    attention.mesh_devices(self._arenas[0])))
                held.update({role: name or "none" for role, name in self.kernels.items()})
            state.update(events.counts())
        self._note_compiles("state", events)
        self.page_bytes = sum(a.nbytes // a.shape[1] for a in self._arenas[self._row_kind])
        # donate the page arenas on real accelerators so the in-place
        # update never copies the arena; CPU jax spams donation warnings
        self._ragged_jit = make_ragged_program(
            self.spec, self.feed_layout, sample_logits=bool(self.sample_output),
            donate=jax.default_backend() != "cpu",
        )

    def _make_params(self, params: Any) -> Any:
        """The weights on the default device: ``params``, or seeded random
        ones when None.  ShardedServingBackend overrides this and
        ``_make_arenas`` to create both already laid out over the TP mesh."""
        import jax

        if params is None:
            params = self.spec.init_params(jax.random.PRNGKey(self._seed))
        return params

    def _make_arenas(self) -> tuple:
        """The zeroed page arenas, in the program's argument order, and the
        zeroed state arrays behind them where the model keeps state."""
        pages = tuple(self.spec.init_arenas(
            self.num_pages, self.page_size, self.num_window_pages))
        if not self.state_slots:
            return pages
        return pages + tuple(self.spec.init_state(self.state_slots))

    def _note_compiles(self, entry: str, events: "startup.ProgramEvents") -> None:
        """Book the compile requests of one backend call: the counter (one a
        request, whatever the cache did) and the next step's report."""
        n = events.compiles
        if not n:
            return
        if self._metrics is not None:
            # every series of a backend says which walk and which form of
            # the grouped expert products its step program holds
            self._metrics.serving_compiles.inc(
                float(n), entry=entry, walk_kernel=self.kernels.get("walk") or "none",
                expert_kernel=self.kernels.get("expert") or "none")
        self._paid[0] += n
        self._paid[1] += events.compile_ns
        self._paid[2] += events.hits

    @contextlib.contextmanager
    def _page_program(self, entry: str) -> Iterator[None]:
        """One of the page programs' calls, under the device lock; what it
        made the compiler do (its first use) is booked like a step's."""
        t0 = time.time_ns()
        with self._dev_lock:
            with startup.backend_call() as events:
                yield
            if events.spans:
                self._note_compiles(entry, events)
                startup.program(entry, t0, events)

    def compiled_programs(self) -> int:
        """Shape keys ``step`` has been called with (what the compiler did
        is the ``step`` span's ``compiled``)."""
        return len(self._compiled_shapes)

    def _clamp(self, row: list[int]) -> list[int]:
        vmax = self.spec.vocab_size - 1
        return [min(max(0, int(t)), vmax) for t in row]

    # ------------------------------------------------------------------
    def step(self, entries: list[StepEntry]) -> list[Any]:
        """One ragged mixed prefill+decode device call.

        Returns one value per entry, aligned: the next token (``int``) for
        sampled entries, ``None`` for prefill chunks that do not complete
        their prompt, and the per-position prediction list (``list[int]``,
        one next-token argmax per fed position) for draft verification
        rows (``entry.draft > 0``).  Blocking; call from an executor
        thread."""
        marks = [time.time_ns()]
        n_step = self._steps_done
        self._ensure()
        if not entries:
            return []
        t_buf, s_rows = self.max_batch_tokens, self.max_seqs
        if len(entries) > s_rows:
            raise ValueError(
                f"{len(entries)} sequences in one step; backend max_seqs is "
                f"{s_rows}"
            )
        total = sum(len(e.tokens) for e in entries)
        if total > t_buf:
            raise ValueError(
                f"{total} tokens in one step; backend max_batch_tokens is "
                f"{t_buf}"
            )
        with step_phase("pack", n_step, marks):
            # one vector a step (never reused: the transfer may still read
            # it when the call returns), packed through its views
            feed = np.zeros((self.feed_layout.size,), np.int32)
            tokens, positions, token_seq, out_idx, tables = self.feed_layout.split(feed)
            state_slot = self.feed_layout.state_slot(feed)  # empty without state
            # padding tokens map to the padding row (all null pages): their
            # writes land on page 0 and no live sequence's gather can see them
            token_seq[:] = s_rows
            ti = 0
            spans: list[tuple[int, int]] = []  # entry i's [lo, hi) buffer slots
            for i, e in enumerate(entries):
                row = self._clamp(e.tokens)
                n = len(row)
                if not n:
                    raise ValueError("empty StepEntry.tokens")
                if e.start + n > self.max_context:
                    raise ValueError(
                        f"entry spans positions [{e.start}, {e.start + n}); "
                        f"backend max_context is {self.max_context}"
                    )
                tokens[ti:ti + n] = row
                positions[ti:ti + n] = np.arange(e.start, e.start + n)
                token_seq[ti:ti + n] = i
                tables[0][i, : len(e.pages)] = e.pages
                if self.window:
                    if not e.window_pages:
                        raise ValueError(
                            "StepEntry.window_pages is empty: this model's window "
                            "layers keep their K and V in a ring of their own")
                    tables[1][i, : len(e.window_pages)] = e.window_pages
                if self.state_slots:
                    if not 0 < e.state_slot < self.state_slots:
                        raise ValueError(
                            f"StepEntry.state_slot {e.state_slot}: this model keeps a row's "
                            f"recurrent state in one of the slots 1..{self.state_slots - 1}")
                    state_slot[i] = e.state_slot
                out_idx[i] = ti + n - 1
                spans.append((ti, ti + n))
                ti += n
            if self.state_slots and len(set(state_slot[:len(entries)].tolist())) < len(entries):
                # the recurrence kernels' pipeline reads a row's state before
                # the rows ahead of it are written back (models/row_pipeline.py)
                raise ValueError("two StepEntry rows name one state_slot: a slot is one "
                                 "session's, a session one row of a step")
            self._compiled_shapes.add(("ragged", t_buf, s_rows, self.pages_per_seq))
        with contextlib.ExitStack() as held:
            # dispatch opens before the lock is taken, so a wait for it
            # shows there; the lock is held until the result is on the host
            with step_phase("dispatch", n_step, marks):
                held.enter_context(self._dev_lock)
                # numpy straight into the call: its one transfer rides the
                # call's own path; the result's copy back starts now, so
                # ``wait`` finds it on the host when the program ends
                with startup.backend_call() as events:
                    nxt, *self._arenas = self._ragged_jit(
                        self._params, *self._arenas, feed)
                nxt.copy_to_host_async()
            if self.on_dispatched is not None:
                self.on_dispatched()
            with step_phase("wait", n_step, marks):
                # two waits and a stamp between them: the program has ended
                # (hand-over to here: launch and program), then the result's
                # way back to the host (``wait.fetch``)
                nxt.block_until_ready()
                ready = time.time_ns()
                with annotation("cordum.wait.fetch", step=n_step):
                    out = np.asarray(nxt)
            if events.spans:  # the call traced or compiled: never in a warm window
                self._note_compiles("ragged", events)
                startup.program("ragged", marks[1], events, ran_until_ns=marks[3])
            n, ns, hits = self._paid
            self.last_step_compiled = n > 0
            if n:
                self.last_compile_ms, self.last_cache_hit = ns / 1e6, hits >= n
                self._paid = [0, 0, 0]
        # out is [T] per-position predictions: a sampled entry's token is
        # the prediction after its LAST fed slot (== out_idx[i], the same
        # value the old sequence-final projection produced); a draft row
        # gets the whole span — one verification vote per fed position
        with step_phase("unpack", n_step, marks):
            res: list[Any] = []
            for e, (lo, hi) in zip(entries, spans):
                if e.draft > 0:
                    res.append([int(t) for t in out[lo:hi]])
                elif e.sample:
                    res.append(int(out[hi - 1]))
                else:
                    res.append(None)
            if self.spec.aux_shape:
                # the family's counters rode behind the tokens, one transfer
                self.last_aux = out[t_buf:].reshape(self.spec.aux_shape)
                if self.spec.count_aux is not None:
                    self.last_counters, self.last_attrs = self.spec.count_aux(
                        self.last_aux, ti, self.kernels)
            # the walk as the program made it: a kind of page's tiles each to
            # their own ends where that kind's walk is a kernel, and a run of
            # one row's tiles sharing a block's copy where it is the by-head one
            from ..models import attention

            own_ends = tuple(bool(self.kernels.get(role)) for role in attention.WALK_ROLES)
            walked, self.last_window_blocks, self.last_attn_rows, self.last_attn_live = (
                attention.count_walk(
                    np.array(spans), positions, self._tile_slots, self._block_tokens,
                    self.window, own_ends, tuple(own and self.kv_by_head for own in own_ends)))
            self.last_attn_blocks = (walked, self._attn_blocks_total)
            if self.on_step is not None:
                self.on_step(entries)
        self._steps_done = n_step + 1
        self.last_phases = tuple(marks)
        self.last_ready_ns = ready
        return res

    # ------------------------------------------------------------------
    # live KV-page migration (serving/migration.py, docs/PROTOCOL.md §Page
    # transfer): pages leave and enter the arena at their TRUE lengths —
    # only the filled slots of each page ride the wire, float32-upcast so
    # the receiver can cast back into its own arena dtype exactly.
    def export_kv(
        self, pages: list[int], start_tok: int, end_tok: int
    ) -> list[dict]:
        """Records for the session pages covering positions
        ``[start_tok, end_tok)``.  ``pages`` is the session's full page
        list; record ``i`` is the page ORDINAL within it (the receiver maps
        ordinals onto its own freshly allocated arena blocks).  Blocking
        (device reads); call from an executor thread."""
        self.spec.require_page_records("page export (migration, hibernation)")
        if end_tok <= start_tok:
            return []
        self._ensure()
        from ..models import attention

        ps = self.page_size
        first, last = start_tok // ps, -(-end_tok // ps)
        ords = list(range(first, min(last, len(pages))))
        used = [min(ps, end_tok - o * ps) for o in ords]
        # under the device lock: on donating backends a concurrent step
        # invalidates the arena buffers it was handed, so the gather must
        # not overlap a step's jit call (page CONTENT below end_tok is
        # stable either way — steps only write at the current positions)
        with self._page_program("gather_page"):
            blocks = attention.gather_kv_pages(
                self._k_pages, self._v_pages, [pages[o] for o in ords], used
            )
        return [
            {"i": o, "used": n, "k": k.tobytes(), "v": v.tobytes(),
             "shape": list(k.shape)}
            for o, n, (k, v) in zip(ords, used, blocks)
        ]

    def import_kv(self, pages: list[int], records: list[dict]) -> None:
        """Scatter migrated page records into freshly allocated arena
        blocks (``pages``, the receiving session's page list).  Blocking;
        call from an executor thread."""
        self.spec.require_page_records("page import (migration, hibernation)")
        if not records:
            return
        self._ensure()
        from ..models import attention

        if any("heads" in rec for rec in records):
            # per-rank records from a serving-gang source (docs/SERVING.md
            # §Sharded serving): each rank exported its head slice of every
            # page — merge the slices back into full-head records, so ANY
            # backend (single-rank or gang) imports a gang export unchanged
            from .shard import merge_rank_records

            records = merge_rank_records(records)
        ids, blocks = [], []
        for rec in records:
            o = int(rec["i"])
            if not 0 <= o < len(pages):
                raise ValueError(f"page ordinal {o} outside {len(pages)} pages")
            shape = tuple(rec["shape"])
            k = np.frombuffer(rec["k"], np.float32).reshape(shape)
            v = np.frombuffer(rec["v"], np.float32).reshape(shape)
            ids.append(pages[o])
            blocks.append((k, v))
        with self._page_program("scatter_page"):
            self._k_pages, self._v_pages = attention.scatter_kv_pages(
                self._k_pages, self._v_pages, ids, blocks
            )

    def copy_page(self, src: int, dst: int) -> None:
        """Duplicate physical page ``src`` into ``dst`` on device — the
        copy-on-write half of prefix sharing (docs/SERVING.md §Prefix
        cache and tiering), in every arena the whole-row kind has (K and V
        by head, or the one latent array).  The engine calls this before any position
        inside a shared page would be written: the writer gets its own
        copy, every other table keeps attending to the original.  One
        cached executable serves every CoW (traced page indices).
        Blocking; call from an executor thread."""
        self.spec.require_whole_row("page copy (prefix sharing)")
        self._ensure()
        from ..models import attention

        with self._page_program("copy_page"):
            self._arenas[self._row_kind] = attention.copy_page(
                self._arenas[self._row_kind], src, dst)

    # ------------------------------------------------------------------
    # compat conveniences over step() — tests and benches drive these; the
    # engine always assembles mixed steps itself.  Both ride the SAME
    # ragged program: there is nothing else to compile.
    def prefill(self, prompt: list[int], pages: list[int]) -> int:
        """Run a whole prompt through ragged prefill chunks (token-budget
        sized) and return the first generated token.  Blocking."""
        row = list(prompt)[: self.max_context]
        total = max(1, len(row)) or 1
        first: Optional[int] = None
        start = 0
        while start < total or first is None:
            chunk = row[start:start + self.max_batch_tokens] or [0]
            done = start + len(chunk) >= total
            (first,) = self.step([StepEntry(
                tokens=chunk, start=start, pages=pages, sample=done,
                phase="prefill",
            )])
            start += len(chunk)
            if done:
                break
        assert first is not None
        return first

    def decode(self, entries: list[tuple[int, int, list[int]]]) -> list[int]:
        """One decode step for a ragged batch of ``(last_token, position,
        pages)`` triples — one next token per entry.  Batches wider than
        the static shapes split across step() calls.  Blocking."""
        out: list[int] = []
        width = min(self.max_seqs, self.max_batch_tokens)
        for lo in range(0, len(entries), width):
            chunk = entries[lo:lo + width]
            res = self.step([StepEntry(
                tokens=[tok], start=pos, pages=pages, sample=True,
                phase="decode",
            ) for tok, pos, pages in chunk])
            out.extend(int(t) for t in res if t is not None)
        return out


#: the name the class had while the llama family was the only one; the same
#: class, kept for its importers
LlamaServingBackend = ServingBackend
