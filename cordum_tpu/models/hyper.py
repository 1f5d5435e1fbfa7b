"""Manifold-constrained hyper-connections (mHC; Xie et al., DeepSeek, arXiv
2512.24880, on Hyper-Connections, Zhu et al., arXiv 2409.19606): the two maps
that bracket a sublayer of a model whose residual is ``n`` streams a token
(docs/SERVING.md §The hyper-connected stream; ``models/xing.py`` is the
family that runs them).

A token's stream is ``X`` in ``R^{n x C}``, float32, kept in the program as
``[T, n x C]``: the ``n`` streams side by side on the minor axis (a ``[T, n,
C]`` array would pad 4 sublanes to 8 in a TPU's tiled layout and every
reshape of it would be a copy).  Round a sublayer ``F``, with the sublayer's
own ``phi`` [n (n + 2), n C] (rows: ``Phi_pre^T`` | ``Phi_post^T`` |
``Phi_res^T``, the last row-major over ``(i, j)``), ``alpha`` [3] and ``bias``
[n (n + 2)] (``b_pre`` | ``b_post`` | ``B_res``):

    u  = vec(X);  u^ = u / sqrt(mean(u^2) + norm_eps)            (no gain)
    z  = u^ phi^T                                                [n (n + 2)]
    H_pre  = sigmoid(alpha_pre z_pre + b_pre)                    [n]
    H_post = 2 sigmoid(alpha_post z_post + b_post)               [n]
    M = exp(clamp(alpha_res z_res + B_res, clamp_min, clamp_max))   [n, n]
    ``iters`` times:  M <- M / (column sums + eps);  M <- M / (row sums + eps)
    H_res = M                      (Sinkhorn-Knopp: rows sum to 1 at the end)

    h  = sum_j H_pre[j] X[j]                    :func:`mhc_open`
    y  = F(norm(h))                             the sublayer, not this module's
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y        :func:`mhc_close`

Everything here is float32; ``phi`` is kept in the weights' dtype (a
checkpoint's) and read exactly.  The three maps travel from the open to the
close as ONE array ``maps`` float32 ``[T, 128]``: columns ``H_pre | H_post |
H_res`` (row-major), zeros behind them (:func:`split_maps`): a lane-dense
row a token, which the close reads with tokens on sublanes as it needs them.

Each map in two forms, chosen where the program is LOWERED
(``jax.lax.platform_dependent``, as ``models/latent_walk.py``'s walk) and by
the operands' shapes (:func:`holds_kernel`):

* **Pallas TPU kernels** :data:`OPEN_KERNEL` / :data:`CLOSE_KERNEL`, under
  those names in the lowered program and in a device trace.  A grid step
  takes a TILE of tokens whose stream is held in VMEM, so ``mhc_open`` reads
  ``X`` ONCE for the norm, the projection and the contraction.  The
  projection is an MXU product with tokens on LANES (``phi`` [rows, n C]
  against the tile ``[tokens, n C]``, both contracted on their minor axis),
  float32-exact in two bfloat16 passes: ``phi`` is bfloat16 already, the
  stream is split into its bfloat16 head and the bfloat16 of what is left
  (an error of 2^-17 of a stream's number).  The ``n^2`` entries of ``M`` are
  ``n^2`` vectors over the tile's tokens, Sinkhorn a loop over registers;
  two 128 x 128 transposes bring the norm's scale to lanes and the finished
  maps back to sublanes.  Padded slots are computed like live ones (a slot's
  maps depend on that slot alone).
* ``jax.numpy`` (:func:`open_jnp`, :func:`close_jnp`): every other platform,
  the fallback, and the tests' yardstick.  The maps' arithmetic is ONE
  function (:func:`maps_of`) that both forms call.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp

#: the platform whose lowering of the two maps holds the kernels
PLATFORM = "tpu"
#: the kernels' names in the lowered program (their custom calls) and in a trace
OPEN_KERNEL = "mhc_open"
CLOSE_KERNEL = "mhc_close"
LANES = 128  # a TPU tile's minor dimension, and the width of ``maps``
#: tokens a grid step of each kernel takes.  The open's is the side of its two
#: transposes; the close holds the tile's stream twice (in and out)
OPEN_TILE = 128
CLOSE_TILE = 64
#: VMEM either kernel may ask for (a v5e core has 128 MiB, the compiler grants
#: 16 by default): :func:`vmem_bytes` at the published widths is 31 MiB
VMEM_BUDGET_BYTES = 48 * 1024 * 1024
HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Hyper:
    """What the maps need of a configuration (``hc_mult``,
    ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min / max``,
    ``rms_norm_eps``)."""
    n: int = 4
    iters: int = 20
    eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    norm_eps: float = 1e-6

    def __post_init__(self) -> None:
        if not 1 <= self.n or self.rows > LANES:
            raise ValueError(f"{self.n} streams: the maps' {self.rows} numbers a token "
                             f"travel in one row of {LANES}")

    @property
    def rows(self) -> int:
        """Numbers of the three maps a token: ``n + n + n^2``."""
        return self.n * (self.n + 2)


def init_params(key: jax.Array, hc: Hyper, width: int, dtype: Any) -> dict:
    """One sublayer's maps, seeded so that nothing a trained model would have
    fitted is drawn and the token-dependent part DECIDES them: ``phi``
    normal(0, 1/sqrt(n C)) (``u^ phi^T`` of unit spread), ``alpha`` 1, ``b_pre
    = b_post = 0`` (``H_post`` 1 on average: every branch at unit gain),
    ``B_res = 2 I`` (a mixing that mostly keeps a stream)."""
    phi = jax.random.normal(key, (hc.rows, hc.n * width), jnp.float32) / (hc.n * width) ** 0.5
    return {"phi": phi.astype(dtype), "alpha": jnp.ones((3,), jnp.float32),
            "bias": seeded_bias(hc.n)}


def seeded_bias(n: int) -> jax.Array:
    """``b_pre = b_post = 0 | B_res = 2 I``, float32 [n (n + 2)]."""
    return jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                            2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])


def split_maps(maps: jax.Array, n: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``maps`` [T, 128] -> ``(H_pre [T, n], H_post [T, n], H_res [T, n, n])``."""
    return (maps[:, :n], maps[:, n:2 * n],
            maps[:, 2 * n:n * (n + 2)].reshape(maps.shape[0], n, n))


def maps_of(z: Sequence[Any], alpha: Sequence[Any], bias: Sequence[Any], hc: Hyper) -> list:
    """The three maps from ``z``, the ``n (n + 2)`` projections of the normed
    stream: a sequence of arrays of ONE shape, tokens laid out however the
    caller has them (a ``[1, tile]`` row of lanes in the kernel, ``[T]`` in
    :func:`open_jnp`); ``alpha`` and ``bias`` sequences of scalars.  Returns
    the maps in ``maps``' column order, arrays of that shape.  THE writing of
    the maps' arithmetic: both forms call it."""
    n = hc.n
    pre = [jax.nn.sigmoid(alpha[0] * z[j] + bias[j]) for j in range(n)]
    post = [2.0 * jax.nn.sigmoid(alpha[1] * z[n + j] + bias[n + j]) for j in range(n)]
    m = tuple(jnp.exp(jnp.clip(alpha[2] * z[2 * n + k] + bias[2 * n + k],
                               hc.clamp_min, hc.clamp_max)) for k in range(n * n))

    def normalise(m: list, lines: Sequence[Sequence[int]]) -> list:
        out = list(m)
        for line in lines:
            total = m[line[0]]
            for k in line[1:]:
                total = total + m[k]
            inv = 1.0 / (total + hc.eps)
            for k in line:
                out[k] = m[k] * inv
        return out

    columns = [[i * n + j for i in range(n)] for j in range(n)]
    rows = [[i * n + j for j in range(n)] for i in range(n)]

    def sinkhorn(_: Any, m: tuple) -> tuple:
        return tuple(normalise(normalise(list(m), columns), rows))

    return pre + post + list(jax.lax.fori_loop(0, hc.iters, sinkhorn, m))


# ---------------------------------------------------------------------------
# the jax.numpy forms
# ---------------------------------------------------------------------------


def open_jnp(x: jax.Array, phi: jax.Array, alpha: jax.Array, bias: jax.Array,
             hc: Hyper) -> tuple[jax.Array, jax.Array]:
    """``x`` [T, n C] float32 -> ``(h [T, C], maps [T, 128])``."""
    t, n = x.shape[0], hc.n
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1) + hc.norm_eps)  # [T]
    z = jnp.matmul(x, phi.astype(jnp.float32).T, precision=HI) * r[:, None]  # [T, rows]
    got = maps_of([z[:, k] for k in range(hc.rows)], alpha, bias, hc)
    maps = jnp.stack(got + [jnp.zeros((t,), jnp.float32)] * (LANES - hc.rows), axis=1)
    h = jnp.einsum("tj,tjc->tc", maps[:, :n], x.reshape(t, n, -1), precision=HI)
    return h, maps


def close_jnp(x: jax.Array, y: jax.Array, maps: jax.Array, hc: Hyper) -> jax.Array:
    """``x`` [T, n C], ``y`` [T, C] (the sublayer's output, any float dtype),
    ``maps`` [T, 128] -> the next stream [T, n C] float32."""
    t, n = x.shape[0], hc.n
    _, post, res = split_maps(maps, n)
    mixed = jnp.einsum("tij,tjc->tic", res, x.reshape(t, n, -1), precision=HI)
    return (mixed + post[:, :, None] * y.astype(jnp.float32)[:, None, :]).reshape(t, -1)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def vmem_bytes(n: int, width: int) -> int:
    """The more either kernel keeps in VMEM: the open its tile of the stream
    and of ``h`` (two buffers each), ``phi`` twice, one stream's bfloat16
    head and rest with their float32 source, and the maps' tiles; the close
    its tile of the stream in and out, of ``y`` (counted as float32) and of
    ``maps`` (two buffers each) and one stream's float32 sum."""
    opened = (2 * OPEN_TILE * n * width * 4 + 2 * OPEN_TILE * width * 4
              + 2 * n * (n + 2) * n * width * 2 + OPEN_TILE * width * (4 + 4 + 2 + 2)
              + 6 * OPEN_TILE * LANES * 4)
    closed = (4 * CLOSE_TILE * n * width * 4 + 2 * CLOSE_TILE * width * 4
              + 2 * CLOSE_TILE * LANES * 4 + 2 * CLOSE_TILE * width * 4)
    return max(opened, closed)


def fits(n: int, width: int) -> bool:
    """Whether the kernels take streams ``width`` wide: whole lane tiles a
    stream, and within :data:`VMEM_BUDGET_BYTES`.  The number of buffer slots
    does not decide: :func:`padded_slots` brings it to what the tiles need."""
    return width % LANES == 0 and vmem_bytes(n, width) <= VMEM_BUDGET_BYTES


def padded_slots(t_buf: int) -> int:
    """Slots the kernels compute for a buffer of ``t_buf``: whole tiles of
    the open (128, the side of its transposes), which the close's 64 divides,
    so that NO partial tile ever reaches Mosaic.  THE rule, for the wrappers
    and for a step program alike: a partial last tile inside a whole step
    program hung the chip twice (PERF.md section 6, PR 49: 240 slots; each
    kernel alone at 240 ended and agreed), and it is the one thing of these
    kernels the interpreter cannot vouch for."""
    return -(-t_buf // OPEN_TILE) * OPEN_TILE


def step_slots(t_buf: int, n: int, width: int) -> int:
    """Slots a step program brings its buffer to before the first map, so
    that the wrappers below find it whole and pad (copy) nothing:
    :func:`padded_slots` where the kernels fit, the buffer itself at widths
    they do not.  It is also what a step's ``mhc_slots`` counts a sublayer."""
    return padded_slots(t_buf) if fits(n, width) else t_buf


def holds_kernel(platform: str, fit: bool) -> bool:
    """Whether a step program lowered for ``platform`` computes the maps with
    the kernels: the platform and the operands' shapes (:func:`fits`),
    nothing else."""
    return fit and platform == PLATFORM


def residual_label(platform: str, n: int, width: int) -> dict[str, str]:
    """The ``residual`` role of a hyper-connected family's
    ``ModelSpec.kernels``: the kernels' names where the lowering for
    ``platform`` holds them at these widths, "" where the maps are
    ``jax.numpy``'s."""
    held = holds_kernel(platform, fits(n, width))
    return {"residual": f"{OPEN_KERNEL}+{CLOSE_KERNEL}" if held else ""}


def _open_kernel(scal_ref, x_ref, phi_ref, h_ref, maps_ref, rows_ref, *, hc: Hyper, width: int):
    """One tile of tokens.  ``scal_ref`` (SMEM): ``alpha`` then ``bias``;
    ``x_ref`` [tile, n C]; ``phi_ref`` [rows, n C]; ``rows_ref`` [128, tile]
    scratch, the maps with tokens on lanes before they are turned."""
    n, tile = hc.n, x_ref.shape[0]
    nt = (((1,), (1,)), ((), ()))  # both operands contracted on their minor axis
    ssq = jnp.zeros((tile, 1), jnp.float32)
    z = jnp.zeros((hc.rows, tile), jnp.float32)
    for j in range(n):  # a stream at a time: the temporaries stay one stream wide
        xj = x_ref[:, j * width:(j + 1) * width]
        ssq = ssq + jnp.sum(xj * xj, axis=1, keepdims=True)
        head = xj.astype(jnp.bfloat16)
        rest = (xj - head.astype(jnp.float32)).astype(jnp.bfloat16)
        pj = phi_ref[:, j * width:(j + 1) * width]
        z = z + (jax.lax.dot_general(pj, head, nt, preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(pj, rest, nt, preferred_element_type=jnp.float32))
    r = jax.lax.rsqrt(ssq * (1.0 / (n * width)) + hc.norm_eps)  # [tile, 1]: tokens on sublanes
    r_lanes = jnp.broadcast_to(r, (tile, LANES)).T[0:1, :]  # [1, tile]: tokens on lanes
    got = maps_of([z[k:k + 1, :] * r_lanes for k in range(hc.rows)],
                  [scal_ref[k] for k in range(3)],
                  [scal_ref[3 + k] for k in range(hc.rows)], hc)
    rows_ref[...] = jnp.zeros_like(rows_ref)
    for k, row in enumerate(got):
        rows_ref[k:k + 1, :] = row
    maps = rows_ref[...].T  # [tile, 128]: tokens back on sublanes
    maps_ref[...] = maps
    h = maps[:, 0:1] * x_ref[:, 0:width]
    for j in range(1, n):
        h = h + maps[:, j:j + 1] * x_ref[:, j * width:(j + 1) * width]
    h_ref[...] = h


def open_kernel(x: jax.Array, phi: jax.Array, alpha: jax.Array, bias: jax.Array,
                hc: Hyper) -> tuple[jax.Array, jax.Array]:
    """The same as :func:`open_jnp` through the Pallas kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_buf, width = x.shape[0], x.shape[1] // hc.n
    t = padded_slots(t_buf)
    x = jnp.pad(x, ((0, t - t_buf), (0, 0))) if t > t_buf else x
    scal = jnp.concatenate([alpha.astype(jnp.float32), bias.astype(jnp.float32)])
    h, maps = pl.pallas_call(
        partial(_open_kernel, hc=hc, width=width),
        out_shape=(jax.ShapeDtypeStruct((t, width), jnp.float32),
                   jax.ShapeDtypeStruct((t, LANES), jnp.float32)),
        grid=(t // OPEN_TILE,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((OPEN_TILE, hc.n * width), lambda i: (i, 0)),
                  pl.BlockSpec((hc.rows, hc.n * width), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((OPEN_TILE, width), lambda i: (i, 0)),
                   pl.BlockSpec((OPEN_TILE, LANES), lambda i: (i, 0))),
        scratch_shapes=[pltpu.VMEM((LANES, OPEN_TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=OPEN_KERNEL,
    )(scal, x, phi)
    return h[:t_buf], maps[:t_buf]


def _close_kernel(x_ref, y_ref, maps_ref, out_ref, *, n: int, width: int):
    """One tile of tokens: every stream of the next stream from the tile's
    streams, its maps a column a number (tokens on sublanes, as the products
    broadcast them)."""
    maps = maps_ref[...]
    y = y_ref[...].astype(jnp.float32)
    for i in range(n):
        acc = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            acc = acc + maps[:, k:k + 1] * x_ref[:, j * width:(j + 1) * width]
        out_ref[:, i * width:(i + 1) * width] = acc


def close_kernel(x: jax.Array, y: jax.Array, maps: jax.Array, hc: Hyper) -> jax.Array:
    """The same as :func:`close_jnp` through the Pallas kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_buf, width = y.shape
    t = padded_slots(t_buf)
    if t > t_buf:
        x, y, maps = (jnp.pad(a, ((0, t - t_buf), (0, 0))) for a in (x, y, maps))
    return pl.pallas_call(
        partial(_close_kernel, n=hc.n, width=width),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grid=(t // CLOSE_TILE,),
        in_specs=[pl.BlockSpec((CLOSE_TILE, hc.n * width), lambda i: (i, 0)),
                  pl.BlockSpec((CLOSE_TILE, width), lambda i: (i, 0)),
                  pl.BlockSpec((CLOSE_TILE, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((CLOSE_TILE, hc.n * width), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name=CLOSE_KERNEL,
    )(x, y, maps)[:t_buf]


# ---------------------------------------------------------------------------
# the two maps, by the form the lowering platform holds
# ---------------------------------------------------------------------------


# jitted, so that the sublayers of a step program trace each map ONCE (the
# kernel's body is a few thousand equations)
@partial(jax.jit, static_argnames=("hc",))
def mhc_open(x: jax.Array, params: dict, hc: Hyper) -> tuple[jax.Array, jax.Array]:
    """Before a sublayer: the stream ``x`` [T, n C] float32 and the
    sublayer's maps' parameters (``phi``, ``alpha``, ``bias``) -> ``(h [T,
    C], maps [T, 128])``, both float32: what the sublayer's pre-norm reads,
    and what :func:`mhc_close` needs (:func:`split_maps`)."""
    args = (x, params["phi"], params["alpha"], params["bias"])
    with jax.named_scope(OPEN_KERNEL):
        if not fits(hc.n, x.shape[1] // hc.n):
            return open_jnp(*args, hc)
        return jax.lax.platform_dependent(
            *args, default=partial(open_jnp, hc=hc), **{PLATFORM: partial(open_kernel, hc=hc)})


@partial(jax.jit, static_argnames=("hc",))
def mhc_close(x: jax.Array, y: jax.Array, maps: jax.Array, hc: Hyper) -> jax.Array:
    """Behind a sublayer: the stream it was opened from, the sublayer's
    output ``y`` [T, C] and the open's ``maps`` -> the next stream [T, n C]
    float32."""
    with jax.named_scope(CLOSE_KERNEL):
        if not fits(hc.n, y.shape[1]):
            return close_jnp(x, y, maps, hc)
        return jax.lax.platform_dependent(
            x, y, maps, default=partial(close_jnp, hc=hc),
            **{PLATFORM: partial(close_kernel, hc=hc)})


__all__ = ["CLOSE_KERNEL", "Hyper", "OPEN_KERNEL", "PLATFORM", "close_jnp", "close_kernel",
           "fits", "holds_kernel", "init_params", "maps_of", "mhc_close", "mhc_open", "open_jnp",
           "open_kernel", "padded_slots", "residual_label", "seeded_bias", "split_maps", "step_slots"]
