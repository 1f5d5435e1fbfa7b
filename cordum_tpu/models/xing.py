"""Xing4.0 decoder (XingChen-AGI, ``model_type: xing4_0``: the DeepSeek-V3
line of keys plus ``hc_mult`` / ``hc_sinkhorn_iters`` / ``hc_eps`` /
``mhc_h_res_clamp_*``) on the serving path.

The tenth block writing, and the first whose RESIDUAL is not one vector a
token (docs/SERVING.md §The hyper-connected stream):

  * **a residual stream of ``hc_mult`` hyper-connected streams** — every
    other family adds a sublayer's output to one residual vector (``x = x +
    o``); here a token keeps ``n`` streams, float32 ``[T, n x d]``, and both
    adds of every block are manifold-constrained hyper-connections
    (``models/hyper.py``): per token, from the normed flattened stream, a
    contraction ``n -> 1`` before the sublayer, an expansion ``1 -> n``
    behind it and an ``n x n`` mixing matrix projected onto the doubly
    stochastic matrices by Sinkhorn-Knopp iterations.  The stream enters as
    the embedding in every stream and leaves as the streams' sum;
  * **the sublayers themselves are A.X-K1's, called and not copied** —
    ``axk1.attention_branch`` (``mla_sublayer`` over ONE latent arena, YaRN)
    and ``axk1.feed_forward_branch`` (a leading dense layer, then
    ``afmoe.expert_layer`` under ``afmoe.route``: sigmoid scores, one group,
    a selection bias in the choice only, one shared expert), each over its
    own pre-norm of what the open map contracted;
  * **the expert set whole** — ``first_expert`` 0 and ``experts_held`` =
    ``n_experts`` in the benchmark's configuration (a share is honoured as
    in every sparse family).

The configuration is A.X-K1's with the maps' five numbers
(:class:`XingConfig` extends ``Axk1Config``); the arena, the walk's rows and
the tail behind the last layer are that module's.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from . import axk1, hyper
from .afmoe import step_report
from .axk1 import Axk1Config, init_arenas

Params = dict


@dataclass(frozen=True)
class XingConfig(Axk1Config):
    n_group: int = 1
    topk_group: int = 1
    route_scale: float = 2.0
    hc_mult: int = 4  # streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6  # beside a column's or row's sum, in every division
    hc_clamp_min: float = -30.0  # mhc_h_res_clamp_min / max: the mixing logits,
    hc_clamp_max: float = 30.0  # before the exponential

    @property
    def hyper(self) -> hyper.Hyper:
        return hyper.Hyper(self.hc_mult, self.hc_sinkhorn_iters, self.hc_eps,
                           self.hc_clamp_min, self.hc_clamp_max, self.norm_eps)

    @property
    def n_sublayers(self) -> int:
        """Sublayers a step's stream passes, each between an open and a close."""
        return 2 * self.n_layers

    def serving_spec(self) -> Any:
        return serving_spec(self)


#: a layer's two sets of maps, by the sublayer they bracket
MAPS = ("hc_attn", "hc_ffn")


def init_params(key: jax.Array, cfg: XingConfig) -> Params:
    """``axk1.init_params`` at this configuration, and beside every layer's
    weights its two sublayers' maps (``hyper.init_params``: nothing a trained
    model would have fitted is drawn) and, on an expert layer, the router's
    selection bias at zero (``noaux_tc`` fits it while a model is trained; a
    seeded router over normed inputs is balanced without it)."""
    params = axk1.init_params(key, cfg)
    keys = jax.random.split(jax.random.fold_in(key, 1), cfg.n_layers)
    layers = []
    for li, (layer, lk) in enumerate(zip(params["layers"], keys)):
        ka, kf = jax.random.split(lk)
        layer = {**layer, MAPS[0]: hyper.init_params(ka, cfg.hyper, cfg.d_model, cfg.dtype),
                 MAPS[1]: hyper.init_params(kf, cfg.hyper, cfg.d_model, cfg.dtype)}
        if li >= cfg.n_dense_layers:
            layer["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        layers.append(layer)
    return {**params, "layers": layers}


def ragged_step(
    params: Params,
    c_pages: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    token_seq: jax.Array,
    out_idx: jax.Array,
    cfg: XingConfig,
    *,
    sample_logits: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One ragged mixed prefill+decode step (the contract of
    ``llama.ragged_step``) over the latent arena ``c_pages`` [L, N, ps,
    latent_width].  Returns ``(out, c_pages)``, ``out`` int32 [T + (expert
    layers + 1) x experts_held]: the per-slot next-token argmax, the
    assignments each held expert got in each expert layer, and one more row
    whose first number is the buffer slots the maps computed this step (a
    sublayer's, times the sublayers)."""
    hc, d = cfg.hyper, cfg.d_model
    # whole tiles for the maps' kernels: the slots behind the buffer are
    # padding slots like any other (the padding row, position 0, the null page)
    t_buf = tokens.shape[0]
    more = hyper.step_slots(t_buf, hc.n, d) - t_buf
    if more:
        tokens, positions = jnp.pad(tokens, (0, more)), jnp.pad(positions, (0, more))
        token_seq = jnp.pad(token_seq, (0, more), constant_values=page_tables.shape[0] - 1)
    live = token_seq < page_tables.shape[0] - 1  # the last row is the padding row
    counts = []
    dt = params["embed"].dtype
    rows = axk1.walk_rows(c_pages, positions, page_tables, token_seq, cfg, dt)
    rope_fn = lambda x, pos: axk1.rope(x, pos, cfg)  # noqa: E731
    with jax.named_scope("embed"):
        # the embedding in every stream: [T, n x d], float32 throughout
        x = jnp.tile(params["embed"][tokens].astype(jnp.float32), (1, hc.n))
    for li, layer in enumerate(params["layers"]):
        h, maps = hyper.mhc_open(x, layer[MAPS[0]], hc)
        o, c_pages = axk1.attention_branch(h, layer, c_pages, li, rows, cfg, rope_fn, dt)
        x = hyper.mhc_close(x, o, maps, hc)
        h, maps = hyper.mhc_open(x, layer[MAPS[1]], hc)
        f, n = axk1.feed_forward_branch(h, layer, li, cfg, live, dt)
        if n is not None:
            counts.append(n)
        x = hyper.mhc_close(x, f, maps, hc)
    with jax.named_scope("mhc_exit"):
        streams = [x[:, j * d:(j + 1) * d] for j in range(hc.n)]
        x = sum(streams[1:], streams[0])
    slots = tokens.shape[0] * cfg.n_sublayers
    counts.append(jnp.zeros((cfg.experts_held,), jnp.int32).at[0].set(slots))
    out = axk1.sampled(x, params, counts, cfg, sample_logits)
    return (jnp.concatenate([out[:t_buf], out[t_buf + more:]]) if more else out), c_pages


def step_counters(cfg: XingConfig, aux: Any, live_tokens: int,
                  kernels: Mapping[str, str]) -> tuple[dict[str, int], dict[str, str]]:
    """``ModelSpec.count_aux``: the expert layer's report as every sparse
    family names it (``afmoe.step_report`` over the expert layers' rows) plus
    ``mhc_slots`` (buffer slots through the maps this step, padding among
    them: the last row's first number) and ``mhc_live`` (live tokens among
    them), so a reader can tell padded work; the ``step`` span says which
    form of the maps the program holds."""
    counters, attrs = step_report(cfg, aux[:-1], live_tokens, kernels)
    counters.update(mhc_slots=int(aux[-1, 0]), mhc_live=live_tokens * cfg.n_sublayers)
    attrs.update(residual_kernel=kernels.get("residual") or "none",
                 mhc_live=str(counters["mhc_live"]))
    return counters, attrs


def serving_spec(cfg: XingConfig) -> Any:
    """The family's specification for the serving backend
    (``serving/modelspec.py``): A.X-K1's one kind of page with ONE latent
    arena, the experts' counts and the maps' slots behind the tokens, and a
    fourth kernel role, ``residual``."""
    from ..serving.modelspec import ModelSpec

    def program(sample_logits):
        def ragged_program(p, cp, toks, pos, pt, ts, oi):
            return ragged_step(p, cp, toks, pos, pt, ts, oi, cfg, sample_logits=sample_logits)

        return ragged_program

    return ModelSpec(
        family="xing", cfg=cfg, vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
        init_params=lambda key: init_params(key, cfg),
        init_arenas=lambda n, ps, _w: init_arenas(cfg, n, ps),
        program=program, arenas=(((cfg.latent_width,),),), value_dim=cfg.kv_rank,
        aux_shape=(cfg.n_expert_layers + 1, cfg.experts_held),
        count_aux=lambda aux, live, kernels: step_counters(cfg, aux, live, kernels),
        kernels=lambda platform, mesh_devices: {
            **axk1.held_kernels(cfg, platform, mesh_devices),
            **hyper.residual_label(platform, cfg.hc_mult, cfg.d_model)},
    )


__all__ = ["MAPS", "XingConfig", "init_arenas", "init_params", "ragged_step", "serving_spec",
           "step_counters"]
