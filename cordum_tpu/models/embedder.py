"""Batched text embedder: the context engine's TPU compute path.

The reference context engine is CPU string-ops only (``core/context/engine/
service.go``); the north star moves its embedding/window ops onto the TPU
worker pool (BASELINE.json: "context-engine embeds/sec" is a headline
metric).  This model is that path: a small transformer encoder with mean
pooling and L2 normalization, fed by a deterministic hashing tokenizer (no
external vocab files — embeddings are for similarity/recall inside the
control plane, not for generation).

TPU-first: bfloat16 params, batch-only sharding (``dp``; embedding batches
are wide and the model is small, so data parallel over the slice is the
right mapping — tensor parallel would waste ICI on tiny matmuls), static
``max_len`` so XLA compiles one program per batch bucket.
"""
from __future__ import annotations

import hashlib
import math
import re
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Params = dict


@dataclass(frozen=True)
class EmbedderConfig:
    vocab_size: int = 32768  # hash buckets
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 4
    d_ff: int = 1024
    max_len: int = 128
    dtype: Any = jnp.bfloat16


_TOKEN_RE = re.compile(r"[a-z0-9]+|[^\sa-z0-9]")


def tokenize(text: str, cfg: EmbedderConfig) -> list[int]:
    """Deterministic hashing tokenizer: lowercase word/punct split, each
    token hashed into [2, vocab); 0 = pad, 1 = CLS."""
    toks = _TOKEN_RE.findall(text.lower())[: cfg.max_len - 1]
    ids = [1]
    for t in toks:
        h = int.from_bytes(hashlib.blake2b(t.encode(), digest_size=4).digest(), "big")
        ids.append(2 + h % (cfg.vocab_size - 2))
    return ids


def token_count(text: str, cfg: EmbedderConfig) -> int:
    """Exact tokenized length of ``text`` (CLS included, capped at max_len)
    without building the row — the micro-batcher's length-bucket key."""
    return min(1 + len(_TOKEN_RE.findall(text.lower())), cfg.max_len)


def batch_tokenize(
    texts: Sequence[str], cfg: EmbedderConfig, *, max_len: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """(ids [B, L] int32, mask [B, L] float32); ``L`` = ``max_len`` (bucket
    length, capped at the model max) or the model max when 0."""
    length = min(max_len, cfg.max_len) if max_len else cfg.max_len
    b = len(texts)
    ids = np.zeros((b, length), np.int32)
    mask = np.zeros((b, length), np.float32)
    for i, t in enumerate(texts):
        row = tokenize(t, cfg)[:length]
        ids[i, : len(row)] = row
        mask[i, : len(row)] = 1.0
    return ids, mask


def init_params(key: jax.Array, cfg: EmbedderConfig) -> Params:
    keys = jax.random.split(key, cfg.n_layers + 2)
    d, f = cfg.d_model, cfg.d_ff

    def dense(k, shape, scale_dim):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(scale_dim)).astype(cfg.dtype)

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i], 6)
        layers.append(
            {
                "norm1": jnp.ones((d,), cfg.dtype),
                "wqkv": dense(lk[0], (d, 3 * d), d),
                "wo": dense(lk[1], (d, d), d),
                "norm2": jnp.ones((d,), cfg.dtype),
                "w1": dense(lk[2], (d, f), d),
                "w2": dense(lk[3], (f, d), f),
            }
        )
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, d), d),
        "pos": dense(keys[-1], (cfg.max_len, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
    }


def _layer_norm(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def forward(params: Params, ids: jax.Array, mask: jax.Array, cfg: EmbedderConfig) -> jax.Array:
    """[B, max_len] ids + mask → [B, d_model] L2-normalized embeddings."""
    b, t = ids.shape
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    x = params["embed"][ids] + params["pos"][None, :t]
    attn_bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e30).astype(jnp.float32)
    for layer in params["layers"]:
        y = _layer_norm(x, layer["norm1"])
        qkv = (y @ layer["wqkv"]).reshape(b, t, 3, h, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        probs = jax.nn.softmax(scores + attn_bias, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, cfg.d_model)
        x = x + attn @ layer["wo"]
        y = _layer_norm(x, layer["norm2"])
        x = x + jax.nn.gelu(y @ layer["w1"]) @ layer["w2"]
    x = _layer_norm(x, params["final_norm"]).astype(jnp.float32)
    pooled = jnp.sum(x * mask[..., None], axis=1) / jnp.maximum(
        jnp.sum(mask, axis=1, keepdims=True), 1.0
    )
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6)


class Embedder:
    """Convenience wrapper holding params + a jitted forward, with optional
    dp sharding over a mesh."""

    def __init__(self, cfg: EmbedderConfig | None = None, *, seed: int = 0, mesh=None):
        self.cfg = cfg or EmbedderConfig()
        self.params = init_params(jax.random.PRNGKey(seed), self.cfg)
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            self.params = jax.tree.map(lambda x: jax.device_put(x, repl), self.params)
            self._data_sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        else:
            self._data_sharding = None
        def embed_forward(p, i, m):  # named: compile logs and traces find it
            return forward(p, i, m, self.cfg)

        self._fwd = jax.jit(embed_forward)

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        ids, mask = batch_tokenize(texts, self.cfg)
        return self.embed_tokens(ids, mask)

    def embed_tokens(self, ids: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Forward pre-tokenized (already padded/bucketed) rows — the
        micro-batcher's entry point; ``embed`` is tokenizer + this."""
        b = ids.shape[0]
        if self._data_sharding is not None:
            pad = -b % self.mesh.devices.size
            if pad:
                ids = np.pad(ids, ((0, pad), (0, 0)))
                mask = np.pad(mask, ((0, pad), (0, 0)))
            ids = jax.device_put(ids, self._data_sharding)
            mask = jax.device_put(mask, self._data_sharding)
        out = np.asarray(self._fwd(self.params, jnp.asarray(ids), jnp.asarray(mask)))
        return out[:b]
