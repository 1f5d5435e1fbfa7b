"""The two maps that bracket a sublayer of a hyper-connected stream
(``cordum_tpu/models/hyper.py``, ISSUE 49): the ``jax.numpy`` form against the
plain reference's equations, the Pallas kernels (interpret mode) against the
``jax.numpy`` form, what Sinkhorn-Knopp leaves after the configured
iterations, the clamp, and the rule that says which form a lowering holds.

Tolerances: the maps are float32 on both sides.  The kernel's projection is
two bfloat16 passes over a float32 stream (an error of 2^-17 of a stream's
number, summed over ``n C`` of them with random signs) and its divisions are a
reciprocal and a product, so the two forms part by 1e-5 at unit-sized streams,
not by rounding alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.families import xing_reference as ref_mod
from cordum_tpu.models import hyper

FORMS = 2e-5  # kernel against jax.numpy, streams and maps of size ~1
PLAIN = 2e-6  # jax.numpy form against the reference's array expressions: float32 rounding


def drawn(t, width, hc, seed=0, y_dtype=jnp.float32):
    kx, kp, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (t, hc.n * width), jnp.float32)
    p = hyper.init_params(kp, hc, width, jnp.bfloat16)
    y = jax.random.normal(ky, (t, width), jnp.float32).astype(y_dtype)
    return x, p, y


@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_plain_form_is_the_references_equations(n):
    hc = hyper.Hyper(n=n)
    x, p, y = drawn(40, 64, hc)
    h, maps = hyper.mhc_open(x, p, hc)  # 64 wide: no kernel fits, whatever the platform
    nxt = hyper.mhc_close(x, y, maps, hc)
    kw = dict(n=n, iters=hc.iters, eps=hc.eps, clamp=(hc.clamp_min, hc.clamp_max),
              norm_eps=hc.norm_eps, static=False)
    x3 = x.reshape(40, n, 64)
    h_ref, post_ref, res_ref = ref_mod.open_maps(x3, p, **kw)
    pre, post, res = hyper.split_maps(maps, n)
    np.testing.assert_allclose(h, h_ref, atol=PLAIN)
    np.testing.assert_allclose(post, post_ref, atol=PLAIN)
    np.testing.assert_allclose(res, res_ref, atol=PLAIN)
    np.testing.assert_allclose(nxt.reshape(40, n, 64),
                               ref_mod.close_maps(x3, y, post_ref, res_ref), atol=4 * PLAIN)
    assert maps.shape == (40, hyper.LANES) and not np.asarray(maps[:, hc.rows:]).any()
    assert float(pre.min()) > 0 and float(pre.max()) < 1 and float(post.max()) < 2


@pytest.mark.parametrize("t,y_dtype", [(144, jnp.float32), (144, jnp.bfloat16), (256, jnp.bfloat16),
                                       (24, jnp.float32)])
def test_the_kernels_equal_the_plain_form(t, y_dtype):
    """144 slots leave the last tile of both kernels partial (128- and
    64-slot tiles), 24 are padded to one of the open's tiles."""
    hc = hyper.Hyper()
    x, p, y = drawn(t, 128, hc, seed=t, y_dtype=y_dtype)
    h0, m0 = hyper.open_jnp(x, p["phi"], p["alpha"], p["bias"], hc)
    x0 = hyper.close_jnp(x, y, m0, hc)
    with pltpu.force_tpu_interpret_mode():
        h1, m1 = hyper.open_kernel(x, p["phi"], p["alpha"], p["bias"], hc)
        x1 = hyper.close_kernel(x, y, m0, hc)
    assert h1.shape == h0.shape and m1.shape == m0.shape and x1.shape == x0.shape
    np.testing.assert_allclose(h1, h0, atol=FORMS)
    np.testing.assert_allclose(m1, m0, atol=FORMS)
    np.testing.assert_allclose(x1, x0, atol=FORMS)


def test_sinkhorn_leaves_a_doubly_stochastic_mixing():
    """Rows last, so they sum to 1 to float32 rounding (``hc_eps`` beside a
    sum of about 1 moves it by 1e-6); columns to within 0.01 after the 20
    iterations at the seeded spread (``B_res = 2 I`` under unit-spread
    logits), and nearer with more iterations."""
    hc = hyper.Hyper()
    x, p, _ = drawn(512, 64, hc, seed=2)
    _, maps = hyper.mhc_open(x, p, hc)
    _, _, res = hyper.split_maps(maps, hc.n)
    assert float(res.min()) > 0
    assert float(jnp.abs(res.sum(axis=2) - 1).max()) < 5e-6
    cols = float(jnp.abs(res.sum(axis=1) - 1).max())
    assert cols < 1e-2
    _, more = hyper.mhc_open(x, p, hyper.Hyper(iters=80))
    assert float(jnp.abs(hyper.split_maps(more, hc.n)[2].sum(axis=1) - 1).max()) < cols / 10
    # a stream mostly keeps itself: the diagonal is the largest entry of most rows
    assert float((jnp.argmax(res, axis=2) == jnp.arange(hc.n)).mean()) > 0.7


def test_the_clamp_is_reached_and_holds():
    """``alpha_res`` 100 drives the mixing logits past +-30 (unit-spread
    projections times 100): the exponential stays finite, and the maps are
    those of logits clipped by hand."""
    hc = hyper.Hyper()
    x, p, _ = drawn(64, 64, hc, seed=3)
    p = {**p, "alpha": jnp.asarray([1.0, 1.0, 100.0], jnp.float32)}
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1) + hc.norm_eps)
    z = jnp.matmul(x, p["phi"].astype(jnp.float32).T, precision=hyper.HI) * r[:, None]
    logits = 100.0 * z[:, 2 * hc.n:] + p["bias"][2 * hc.n:]
    assert float((jnp.abs(logits) > 30).mean()) > 0.5
    _, maps = hyper.mhc_open(x, p, hc)
    res = hyper.split_maps(maps, hc.n)[2]
    assert np.isfinite(np.asarray(maps)).all()
    assert float(jnp.abs(res.sum(axis=2) - 1).max()) < 1e-5
    # the same maps from logits clipped before they reach ``maps_of``
    clipped = jnp.clip(logits, hc.clamp_min, hc.clamp_max) - p["bias"][2 * hc.n:]
    by_hand = hyper.maps_of(
        [z[:, k] for k in range(2 * hc.n)] + [clipped[:, k] / 100.0 for k in range(hc.n ** 2)],
        p["alpha"], p["bias"], hc)
    np.testing.assert_allclose(maps[:, :hc.rows], jnp.stack(by_hand, axis=1), atol=1e-5)
    # and without the clamp the exponential overflows where the logits pass 88
    wide = hyper.Hyper(clamp_min=-1e9, clamp_max=1e9)
    assert not np.isfinite(np.asarray(hyper.mhc_open(x, p, wide)[1])).all()


def test_the_rule_of_the_forms():
    """The kernels where the program is lowered for the TPU and a stream is
    whole lane tiles within the VMEM budget; ``jax.numpy`` everywhere else,
    with no custom call in the CPU's lowering."""
    assert hyper.fits(4, 3584) and hyper.fits(4, 128) and not hyper.fits(4, 64)
    assert not hyper.fits(4, 128 * 64)  # a tile of such streams is past the budget
    assert hyper.vmem_bytes(4, 3584) < hyper.VMEM_BUDGET_BYTES
    assert hyper.residual_label("tpu", 4, 3584) == {"residual": "mhc_open+mhc_close"}
    assert hyper.residual_label("cpu", 4, 3584) == {"residual": ""}
    assert hyper.residual_label("tpu", 4, 64) == {"residual": ""}
    # ONE rule: whole tiles of the open, which the close's tile divides; no partial tile
    assert [hyper.padded_slots(t) for t in (24, 128, 240, 250)] == [128, 128, 256, 256]
    assert hyper.OPEN_TILE % hyper.CLOSE_TILE == 0
    # a step program brings its buffer to that itself, where the kernels fit
    assert [hyper.step_slots(t, 4, 3584) for t in (16, 128, 240, 256)] == [128, 128, 256, 256]
    assert hyper.step_slots(240, 4, 64) == 240
    hc = hyper.Hyper()
    x, p, y = drawn(128, 128, hc)
    text = hyper.mhc_open.lower(x, p, hc).as_text()
    assert "platform_index" in text or "case" in text  # both forms handed to the lowering
    compiled = hyper.mhc_open.lower(x, p, hc).compile().as_text()
    assert "custom_call_target=\"tpu_custom_call\"" not in compiled
    with pytest.raises(ValueError):
        hyper.Hyper(n=12)  # 168 numbers a token do not travel in one row of 128
