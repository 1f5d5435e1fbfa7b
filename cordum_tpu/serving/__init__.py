"""Serving subsystem: paged KV cache + continuous batching (docs/SERVING.md).

The micro-batcher (cordum_tpu/batching) coalesces *stateless* embed/infer
jobs; user-facing LLM traffic is *autoregressive decode* with per-session
state.  This package adds the serving path:

  * :class:`PageAllocator` — block-granular KV-page bookkeeping over a
    preallocated cache arena (page 0 reserved as the null page)
  * :class:`ServingBackend` (``LlamaServingBackend``, its older name) —
    the XLA side, built from a :class:`ModelSpec`: ONE ragged paged-
    attention entry point (:class:`StepEntry` rows over a static flat
    token buffer) serving any mix of prefill chunks and decode steps in a
    single device call — one compiled program, no length/batch buckets
  * :class:`ServingEngine` — the continuous-batching loop: admits new
    sessions and retires finished ones every step, schedules chunked
    prefill *inside* the mixed step under a token budget, streams tokens,
    frees pages on retirement/cancel
  * :class:`MigrationServer` / :func:`migrate_session` — live KV-page
    session migration between workers over the statebus frame layer
    (graceful drain + crash failover, docs/SERVING.md §Migration)

``llm.generate`` jobs route here from the worker intake (see
``worker/runtime.py``); the scheduler pins a conversation's jobs to the
worker holding its KV pages via the ``cordum.session_key`` affinity map
(``controlplane/scheduler/strategy.py``).
"""
from .backend import LlamaServingBackend, ServingBackend, StepEntry
from .engine import (
    GenRequest,
    ServingEngine,
    ServingStats,
    SessionCancelled,
    SessionMigrated,
    SessionRequeued,
)
from .modelspec import ModelSpec, UnsupportedForModel
from .migration import MigrationError, MigrationServer, migrate_session
from .pager import CacheExhausted, PageAllocator

__all__ = [
    "CacheExhausted",
    "GenRequest",
    "LlamaServingBackend",
    "MigrationError",
    "MigrationServer",
    "ModelSpec",
    "PageAllocator",
    "ServingBackend",
    "ServingEngine",
    "ServingStats",
    "SessionCancelled",
    "SessionMigrated",
    "SessionRequeued",
    "StepEntry",
    "UnsupportedForModel",
    "migrate_session",
]
