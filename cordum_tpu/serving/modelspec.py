"""The model seam of the serving path (docs/SERVING.md §Model seam).

:class:`~cordum_tpu.serving.backend.ServingBackend` knows no model family:
it packs host arrays, calls ONE jitted program and keeps what the program
returns.  A :class:`ModelSpec` supplies the rest — the weights' init, the
page arenas, the program — and states what the family's cache can do,
which kernels its lowered program holds (``kernels``) and what its
program's counters are called (``count_aux``): the backend and the engine
hand both on under the family's names and know none of them.

The program's signature is ``(params, *arenas, tokens, positions, *tables,
token_seq, out_idx) -> (out, *arenas)``: one int32 page table ``[S+1,
width]`` per KIND of page (the whole-row kind first; a family with window
layers adds the ring kind), the kind's arenas as the family states them
(``ModelSpec.arenas``: K and V by head for grouped-query attention, ONE
latent array for latent attention), and ``out`` int32
``[T + prod(aux_shape)]`` — the per-slot next tokens, then whatever counters
the family returns in the same transfer.  The backend does not call it with
those operands one by one: ``make_ragged_program`` wraps it as ``(params,
*arenas, feed) -> (out, *arenas)`` under the name the device trace is
searched for, where ``feed`` is the step's ONE packed int32 vector
(``backend.FeedLayout``: ``[tokens T | positions T | token_seq T | out_idx
S | a table per kind]``), taken apart inside the jit.  The signature a
family writes is the one above, unchanged.

A family with **recurrent state** (``ModelSpec.init_state``: linear-attention
layers, ``models/kda.py``) keeps, beside its pages, arrays with NO page axis
and no position: ``[state layers, slots, ...]``, addressed by a session's
**state slot**, which outlives a step.  Its program takes them behind the
page arenas and one more int32 operand, ``state_slot`` ``[S+1]`` (a table
row's slot; 0, the null slot, for the padding row and every unused row),
between the tables and ``token_seq``: ``(params, *arenas, *state, tokens,
positions, *tables, state_slot, token_seq, out_idx) -> (out, *arenas,
*state)``.  A row whose first fed position is 0 starts from a zero state,
whatever its slot held: the program sees that from ``positions``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional


class UnsupportedForModel(RuntimeError):
    """The serving feature cannot run this model family (it assumes that
    every layer's pages cover the whole row under one table): refused
    loudly, never served wrong."""


def require_whole_row(whole_row: bool, feature: str) -> None:
    """Refuse ``feature`` for a model whose window layers live in page rings."""
    if not whole_row:
        raise UnsupportedForModel(
            f"{feature} needs whole-row KV pages; this model keeps its window "
            "layers in page rings (ModelSpec.window)")


def require_page_records(whole_row: bool, by_head: bool, feature: str) -> None:
    """Refuse ``feature``, which carries a session's pages as K and V records
    of whole rows, for a model whose pages are rings or are not K and V by
    head."""
    require_whole_row(whole_row, feature)
    if not by_head:
        raise UnsupportedForModel(
            f"{feature} carries a page as K and V records by head; this model's "
            "page is another thing (ModelSpec.arenas: a latent page has no heads "
            "and no V)")


def require_positional(positional: bool, feature: str) -> None:
    """Refuse ``feature``, which shares, copies, carries or re-feeds
    POSITIONS of a row, for a model that also keeps recurrent state: a state
    has no page to share or ship and cannot be un-advanced."""
    if not positional:
        raise UnsupportedForModel(
            f"{feature} needs a cache that is positions in pages and nothing else "
            "(kv_positional); this model keeps recurrent state in per-session slots "
            "(ModelSpec.init_state), which has no page to share or carry and "
            "cannot be rolled back")


def kv_pair(n_kv_heads: int, head_dim: int) -> tuple[tuple[int, ...], ...]:
    """One kind's arenas as K and V by head: two of ``[kvh, hd]`` a slot."""
    return ((n_kv_heads, head_dim), (n_kv_heads, head_dim))


@dataclass(frozen=True)
class ModelSpec:
    family: str
    cfg: Any
    vocab_size: int
    max_seq_len: int
    #: ``(key) -> params``
    init_params: Callable[[Any], Any]
    #: ``(num_pages, page_size, window_pages) -> arenas``, flat, in the
    #: order of ``arenas``
    init_arenas: Callable[[int, int, int], tuple]
    #: ``(sample_logits) -> ragged_program``, the function that is jitted:
    #: ``(params, *arenas, tokens, positions, *tables, token_seq, out_idx)
    #: -> (out, *arenas)`` with its arguments spelled out (the wrapper
    #: unpacks the step's packed feed into them)
    program: Callable[[bool], Callable[..., tuple]]
    #: the window of the family's window layers; None when every layer sees
    #: the whole row.  A capability (``kv_whole_row``): with a window, a
    #: sequence's pages are of two kinds (whole-row tables and bounded
    #: rings), and what assumes one kind — prefix sharing, hibernation, live
    #: migration, the tensor-parallel gang — refuses the family
    #: (:meth:`require_whole_row`, :meth:`require_page_records`)
    window: Optional[int] = None
    #: per KIND of page (the whole-row kind first), the trailing shape of
    #: each of its arenas behind ``[layers, pages, page_size]``: ``kv_pair``
    #: for K and V by head, ``((640,),)`` for one latent array.  The
    #: backend's page copy maps over a kind's list whatever its length; what
    #: carries a page as K and V records (migration, hibernation, the gang's
    #: head sharding) asks :attr:`kv_by_head` (:meth:`require_page_records`)
    arenas: tuple[tuple[tuple[int, ...], ...], ...] = ()
    #: the width of a value as the attention's walk accumulates it: the head
    #: dimension for K and V by head, the latent's rank for a latent page
    #: (a key's leading columns are its value).  With ``arenas`` it is what
    #: the backend hands ``attention.attn_block_pages``, as the program does
    value_dim: int = 0
    #: ``(slots) -> state arrays``, each ``[state layers, slots, ...]``, in
    #: the program's argument order behind the page arenas; None for a model
    #: whose every cached number lies at a position.  A capability
    #: (``kv_positional``): a state is advanced in place by every step that
    #: feeds its row, so what shares a prefix's pages, re-feeds positions
    #: (speculation's verify rows), or carries a session as page records
    #: (hibernation, migration, the gang) refuses the family
    #: (:func:`require_positional`)
    init_state: Optional[Callable[[int], tuple]] = None
    #: state arrays the program takes and returns behind the page arenas
    n_state: int = 0
    #: shape of the int32 counters behind the tokens in ``out``
    aux_shape: tuple[int, ...] = ()
    #: ``(aux, live_tokens, kernels) -> (counters, attrs)``: the family names
    #: what its counters count, once, for every reader.  ``counters`` is this
    #: step's addend to ``ServingStats.model`` under the family's own names
    #: (and ``backend.last_counters``), ``attrs`` the ``step`` span's
    #: attributes of them (``backend.last_attrs``); ``kernels`` is what
    #: :attr:`kernels` returned for this backend
    count_aux: Optional[Callable[[Any, int, Mapping[str, str]],
                                 tuple[dict[str, int], dict[str, str]]]] = None
    #: ``(platform, mesh_devices) -> {role: kernel's name}``: for the
    #: platform the arenas live on and the number of devices the program is
    #: partitioned over, the kernel the LOWERED step program holds in each
    #: role the family has, among ``walk`` (the attention's walk over the
    #: whole-row kind of page), ``expert`` (the expert layer's grouped
    #: products), ``state`` (a recurrence over state slots), ``residual`` (the
    #: maps that join a sublayer to a residual of several streams:
    #: ``models/hyper.py``).  A role that is
    #: absent or "" is the ``jax.numpy`` / ``ragged_dot`` form.  Built from
    #: the kernel modules' own ``holds_kernel``, the predicate the trace-time
    #: choice uses, so the rule is written once a kernel; the kernels'
    #: modules (Pallas) are imported inside this call
    kernels: Callable[[str, int], Mapping[str, str]] = lambda platform, mesh_devices: {}

    @property
    def kv_whole_row(self) -> bool:
        return self.window is None

    @property
    def n_arenas(self) -> int:
        """Arena arrays the program takes and returns, all kinds together."""
        return sum(len(kind) for kind in self.arenas)

    @property
    def kv_by_head(self) -> bool:
        """Every kind of page is a K, V pair of ``[kvh, hd]`` slots."""
        return all(len(kind) == 2 and all(len(a) == 2 for a in kind) for kind in self.arenas)

    @property
    def kv_positional(self) -> bool:
        """Everything the model keeps of a row lies at a position, in a page."""
        return self.init_state is None

    def require_whole_row(self, feature: str) -> None:
        require_positional(self.kv_positional, feature)
        require_whole_row(self.kv_whole_row, feature)

    def require_page_records(self, feature: str) -> None:
        require_positional(self.kv_positional, feature)
        require_page_records(self.kv_whole_row, self.kv_by_head, feature)


def spec_for(model: Any) -> ModelSpec:
    """A :class:`ModelSpec` from what a caller hands the backend: a spec, or
    a family's config object, which knows its own (``serving_spec()``: each
    family module exports its specification; this module imports no model)."""
    if isinstance(model, ModelSpec):
        return model
    make = getattr(model, "serving_spec", None)
    if make is None:
        raise TypeError(f"no serving model specification for {type(model).__name__}")
    return make()
