"""Tensor-parallel serving over a gang of workers — the sharded half of the
serving backend (docs/SERVING.md §Sharded serving).

One session set, N ranks: a ``serving`` gang (docs/GANG.md) reserves N
co-located workers all-or-nothing through the DeviceLedger, the members
rendezvous, and every rank runs the SAME ragged mixed prefill+decode
program (``models/llama.ragged_step``) over its slice of the model:

  * weights shard Megatron-style per :func:`~cordum_tpu.models.llama.
    param_specs` (column-parallel qkv/gate, row-parallel out/down);
  * both KV page arenas shard by attention head —
    ``[L, num_pages, page_size, kvh, hd]`` split on ``kvh`` — matching the
    column-parallel wk/wv layout so page writes and gathers stay local;
  * **rank 0 alone pays sampling**: follower ranks compile with
    ``sample_logits=False`` (the lm_head projection + argmax are
    dead-code-eliminated) and own nothing but their arena shard.  Rank 0
    owns token streaming, admission, and the session registry.

:func:`rank_mesh` builds the ``tp``-way mesh over the devices JAX exposes
(a four-chip host, or the eight virtual CPU devices of tier-1) and the
weights and arenas genuinely split over it; a host with fewer devices than
``tp`` is an error, not a replica.

Per-rank migration records: :meth:`ShardedServingBackend.export_kv` slices
every PR 12 page record along the head axis and stamps a
``rank``/``tp``/``heads: [lo, hi)`` header;
:func:`merge_rank_records` (called from the base backend's ``import_kv``)
reassembles full-head records from any rank order — so drain, failover,
hand-off, hibernation and the prefix cache keep working when a session's
pages live on N arenas, and a gang export imports into a single-rank
backend (and vice versa) unchanged.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Optional

import numpy as np

from .backend import LlamaServingBackend, StepBackend, StepEntry

__all__ = [
    "heads_for_rank",
    "slice_rank_record",
    "merge_rank_records",
    "entry_to_wire",
    "entry_from_wire",
    "ShardedServingBackend",
    "ServingGangGroup",
]


def heads_for_rank(n_kv_heads: int, tp: int, rank: int) -> tuple[int, int]:
    """The contiguous ``[lo, hi)`` KV-head slice rank ``rank`` owns under a
    ``tp``-way split.  Heads must divide evenly — ragged head splits would
    break the NamedSharding layout."""
    if tp < 1 or not 0 <= rank < tp:
        raise ValueError(f"rank {rank} outside tp={tp}")
    if n_kv_heads % tp:
        raise ValueError(f"{n_kv_heads} kv heads not divisible by tp={tp}")
    per = n_kv_heads // tp
    return rank * per, (rank + 1) * per


def slice_rank_record(rec: dict, rank: int, tp: int, lo: int, hi: int) -> dict:
    """One rank's head slice of a full PR 12 page record.  The wire shape
    stays ``[L, used, heads, hd]`` float32; the header grows ``rank`` /
    ``tp`` / ``heads=[lo, hi)`` so the importer knows where the slice
    lands."""
    shape = tuple(rec["shape"])
    k = np.frombuffer(rec["k"], np.float32).reshape(shape)[:, :, lo:hi]
    v = np.frombuffer(rec["v"], np.float32).reshape(shape)[:, :, lo:hi]
    return {
        "i": rec["i"], "used": rec["used"],
        "k": np.ascontiguousarray(k).tobytes(),
        "v": np.ascontiguousarray(v).tobytes(),
        "shape": list(k.shape),
        "rank": rank, "tp": tp, "heads": [lo, hi],
    }


def merge_rank_records(records: list[dict]) -> list[dict]:
    """Reassemble full-head page records from a per-rank gang export.

    Groups by page ordinal ``i``, orders each group by ``heads[0]``,
    concatenates along the head axis, and checks the slices tile the head
    dimension exactly (contiguous, no gap, no overlap).  Plain full-head
    records pass through untouched, so a mixed list (e.g. a gang export
    appended to a single-rank prefix) merges correctly too."""
    plain = [r for r in records if "heads" not in r]
    sliced = [r for r in records if "heads" in r]
    by_ord: dict[int, list[dict]] = {}
    for rec in sliced:
        by_ord.setdefault(int(rec["i"]), []).append(rec)
    out = list(plain)
    for o, group in sorted(by_ord.items()):
        group = sorted(group, key=lambda r: int(r["heads"][0]))
        ks, vs, cursor = [], [], 0
        for rec in group:
            lo, hi = (int(x) for x in rec["heads"])
            if lo != cursor:
                raise ValueError(
                    f"page {o}: head slice [{lo}, {hi}) does not start at "
                    f"{cursor} — rank records missing or overlapping"
                )
            shape = tuple(rec["shape"])
            ks.append(np.frombuffer(rec["k"], np.float32).reshape(shape))
            vs.append(np.frombuffer(rec["v"], np.float32).reshape(shape))
            cursor = hi
        tp = int(group[0].get("tp", len(group)))
        if len(group) != tp:
            raise ValueError(
                f"page {o}: {len(group)} rank slices for tp={tp}"
            )
        k = np.concatenate(ks, axis=2)
        v = np.concatenate(vs, axis=2)
        out.append({
            "i": o, "used": int(group[0]["used"]),
            "k": np.ascontiguousarray(k).tobytes(),
            "v": np.ascontiguousarray(v).tobytes(),
            "shape": list(k.shape),
        })
    out.sort(key=lambda r: int(r["i"]))
    return out


# ---------------------------------------------------------------------------
# StepEntry wire codec — the serving-gang replay protocol rides GangMsg
# (kind="step") stats dicts, so entries must round-trip through msgpack
# ---------------------------------------------------------------------------


def entry_to_wire(e: StepEntry) -> dict:
    return {
        "tokens": [int(t) for t in e.tokens], "start": int(e.start),
        "pages": [int(p) for p in e.pages], "sample": bool(e.sample),
        "phase": e.phase, "key": e.key, "draft": int(e.draft),
    }


def entry_from_wire(d: dict) -> StepEntry:
    return StepEntry(
        tokens=list(d.get("tokens") or []), start=int(d.get("start", 0)),
        pages=list(d.get("pages") or []), sample=bool(d.get("sample", True)),
        phase=str(d.get("phase", "decode")), key=str(d.get("key", "")),
        draft=int(d.get("draft", 0)),
    )


def rank_mesh(tp: int):
    """The TP mesh this rank's program runs over: ``tp``-way over the
    devices JAX exposes (dp absorbs the rest).  Too few devices, or a count
    ``tp`` does not divide, is an error — never a silent full replica on
    the first device."""
    import jax

    from ..parallel.mesh import simple_mesh

    n = len(jax.devices())
    if n < tp or n % tp:
        raise ValueError(
            f"tp={tp} needs a multiple of {tp} devices; jax exposes {n} "
            f"({jax.default_backend()})")
    return simple_mesh(tp)


class ShardedServingBackend(LlamaServingBackend):
    """One rank of a tensor-parallel serving gang.

    Identical step semantics to :class:`LlamaServingBackend` — same static
    shapes, same ONE compiled program (per rank) — plus:

      * ``rank``/``tp`` identity and the rank's ``[lo, hi)`` KV-head slice;
      * weights + arenas placed with NamedSharding over :func:`rank_mesh`;
      * follower ranks (``rank > 0``) compile with ``sample_logits=False``
        — lm_head never runs there;
      * :meth:`export_kv` emits per-rank head-sliced records (the importer
        side needs no override: the base ``import_kv`` merges them).
    """

    def __init__(self, cfg: Any = None, *, rank: int = 0, tp: int = 1,
                 sample_output: Optional[bool] = None, **kw: Any) -> None:
        super().__init__(cfg, **kw)
        self.spec.require_page_records("the tensor-parallel serving gang (arenas sharded by head)")
        self.rank = int(rank)
        self.tp = max(1, int(tp))
        self.heads = heads_for_rank(self.cfg.n_kv_heads, self.tp, self.rank)
        # rank 0 owns sampling unless the caller says otherwise (the
        # in-process oracle in bench --tp samples on every rank to prove
        # follower outputs are genuinely unused)
        self.sample_output = (self.rank == 0) if sample_output is None else bool(sample_output)
        self.mesh: Any = None

    def _make_params(self, params: Any) -> Any:
        """The weights laid out over the TP mesh.  Seeded weights (and the
        zeroed arenas, below) are CREATED sharded (``jit`` with
        ``out_shardings``): building them on one device first would put the
        whole model there, which at real widths no single chip holds."""
        import jax
        from jax.sharding import NamedSharding

        from ..models import llama

        self.mesh = mesh = rank_mesh(self.tp)
        cfg = self.cfg
        if params is not None:
            return llama.shard_params(params, cfg, mesh)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), llama.param_specs(cfg))

        def sharded_init(key):
            return llama.init_params(key, cfg)

        return jax.jit(sharded_init, out_shardings=shardings)(
            jax.random.PRNGKey(self._seed))

    def _make_arenas(self) -> tuple:
        import jax
        from jax.sharding import NamedSharding

        from ..models import attention

        arena = NamedSharding(self.mesh, attention.KV_ARENA_SPEC)
        return jax.jit(
            lambda: attention.init_kv_pages(self.cfg, self.num_pages, self.page_size),
            out_shardings=(arena, arena),
        )()

    def export_kv(self, pages: list[int], start_tok: int, end_tok: int) -> list[dict]:
        """This rank's head slice of every page record.  A gang's full
        export is the concatenation over ranks — any order; the importer's
        merge sorts by ``heads``.  With ``tp == 1`` the plain full-head
        records ship unchanged."""
        records = super().export_kv(pages, start_tok, end_tok)
        if self.tp <= 1:
            return records
        lo, hi = self.heads
        return [slice_rank_record(r, self.rank, self.tp, lo, hi) for r in records]


class ServingGangGroup(StepBackend):
    """An in-process TP serving gang: rank 0 (the leader, sampling) plus
    ``tp - 1`` followers, driven lock-step as ONE backend — the engine,
    bench ``--tp`` and the property suite use it exactly where a
    :class:`LlamaServingBackend` goes.

    Every rank replays the identical entry batch, so the arenas stay in
    step by construction; step results come from the leader alone (the
    followers' zero buffers are discarded — on real hardware they are never
    even materialized).  Cross-process gangs (worker/gang.py
    ``_run_serving``) are this same loop with the follower ``step()`` calls
    shipped over the bus as ``GangMsg(kind="step")``.
    """

    def __init__(self, cfg: Any = None, *, tp: int = 2, metrics: Any = None,
                 **kw: Any) -> None:
        if tp < 1:
            raise ValueError(f"tp={tp}")
        # metrics ride on the leader only: the group is ONE serving
        # position, and per-rank compile counts stay observable through
        # compiled_per_rank()
        self.ranks = [
            ShardedServingBackend(
                cfg, rank=r, tp=tp, metrics=metrics if r == 0 else None, **kw
            )
            for r in range(tp)
        ]
        self.tp = tp
        self.leader = self.ranks[0]
        self.cfg = self.leader.cfg
        self._forward(self.SHAPES)
        self._lock = threading.Lock()

    def _forward(self, names: tuple[str, ...]) -> None:
        """The contract's members, the leader's."""
        for name in names:
            setattr(self, name, getattr(self.leader, name))

    def compiled_programs(self) -> int:
        return self.leader.compiled_programs()

    def compiled_per_rank(self) -> list[int]:
        return [r.compiled_programs() for r in self.ranks]

    # -- lock-step execution -------------------------------------------
    def step(self, entries: list[StepEntry]) -> list[Any]:
        t0 = time.time_ns()
        if self.on_dispatched is not None:
            self.on_dispatched()  # no one feed: each rank's step feeds and waits in its turn
        with self._lock:
            res = self.leader.step(entries)
            for follower in self.ranks[1:]:
                follower.step(entries)
        self._forward(self.REPORT)
        # any rank paying XLA makes the step a warmup step for the
        # capacity observatory's steady-state filter
        paid = [r for r in self.ranks if r.last_step_compiled]
        self.last_step_compiled = bool(paid)
        if paid:
            self.last_compile_ms = sum(r.last_compile_ms for r in paid)
            self.last_cache_hit = all(r.last_cache_hit for r in paid)
        if self.on_step is not None:
            self.on_step(entries)
        self.stamp_whole_call(t0)
        return res

    def export_kv(self, pages: list[int], start_tok: int, end_tok: int) -> list[dict]:
        out: list[dict] = []
        with self._lock:
            for r in self.ranks:
                out.extend(r.export_kv(pages, start_tok, end_tok))
        return out

    def import_kv(self, pages: list[int], records: list[dict]) -> None:
        # each rank imports the merged full-head records; on real sharded
        # hardware the device_put under NamedSharding lands only the local
        # head slice on each rank's chips
        with self._lock:
            for r in self.ranks:
                r.import_kv(pages, records)

    def copy_page(self, src: int, dst: int) -> None:
        with self._lock:
            for r in self.ranks:
                r.copy_page(src, dst)

    # -- compat conveniences (same contracts as the base backend) ------
    def prefill(self, prompt: list[int], pages: list[int]) -> int:
        return LlamaServingBackend.prefill(self, prompt, pages)  # type: ignore[arg-type]

    def decode(self, entries: list[tuple[int, int, list[int]]]) -> list[int]:
        return LlamaServingBackend.decode(self, entries)  # type: ignore[arg-type]
