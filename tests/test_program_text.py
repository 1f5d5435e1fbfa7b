"""The step programs of the families the benchmark had before ISSUE 42 trace to
the jaxpr text they had (the sixth family, ``falcon_h1``, touches none of
them).  For the four the benchmark had before ISSUE 40: a state slot beside
the page tables, a direct query matrix and a head-wise gate in
``axk1.mla_sublayer`` are additions that a program which does not ask for
them never sees.  The hashes are of the text at tiny sizes (addresses of
function objects cut out); a change that means to alter one of these programs
updates its hash, and says so: ``llama``'s and ``afmoe``'s changed ON
PURPOSE with ISSUE 44, whose ``paged_attention`` hands BOTH walks over K and V
by head to the lowering (``walk_jnp`` and the ``head_walk`` kernel,
``models/head_walk.py``), so both are in the trace; ``afmoe``'s changed ON
PURPOSE again with ISSUE 47, whose kernel walks a window layer's ring too
(a first block a tile, the ring's modulus, the window's lower bound in the
mask: a second, static form of the one kernel), so its window layers hand both
walks to the lowering as its full layers do, while ``llama``'s (no window: the
kernel's ``window=None`` form) is the text it was; the three latent programs
hold ``latent_walk`` as they did and their text is the string it was.
``axk1``'s and ``longcat``'s are ISSUE 41's, whose
expert layer hands BOTH forms of the grouped products to the lowering
(``ragged_dot`` and the ``expert_mlp`` kernel with its work list,
``models/expert_mlp.py``), so both are in the trace; a program without an
expert layer holds neither.  ``bailing``'s is the text on the tree of ISSUE
43: its ``kda_step`` kernel is the token body under the row pipeline
(``models/row_pipeline.py``: a work list of the fed rows in
``kda.state_rows``, two state buffers, a semaphore a buffer and direction),
and ``kda.recurrence`` is jitted with the layer a traced operand, as
``ssd.recurrence`` is, so the KDA layers trace and lower one kernel; the four
others lower no recurrence kernel and keep theirs.  ``axk1``'s and
``bailing``'s changed ON PURPOSE with ISSUE 48: the two families whose
router is group-limited (``n_group`` > 1) find a group's two best and the
kept groups without a sort and without a scatter (``afmoe.route``, scope
``moe_group_select``); ``afmoe``, ``longcat`` and Mellum route with one
group, never enter the scope, and keep their text.  ISSUE 49 wrote
``axk1.ragged_step``'s block body as two branches and a tail that
``models/xing.py`` calls too (the residual adds are the only lines that
differ): ``axk1``'s text is the string it was, and the new family's own
program (``xing``: the same sublayers between the maps of a hyper-connected
stream) joins the table with the text of ISSUE 49's tree.  ``llama``'s and
``afmoe``'s changed ON PURPOSE with ISSUE 50: their trace holds the
``head_walk`` kernel, which is now ONE program a group whose consecutive tiles
on one table row (a run: ``attention.tile_runs``, three more prefetched
scalars a tile) share each block's copy and its taking apart, and
``paged_attention`` computes the tiles' first blocks, trips and runs once a
call where it holds that kernel; the four latent families' programs hold
``latent_walk`` and none of that, and their hashes are the strings they were,
which is the proof that their programs did not move."""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from cordum_tpu.models import (afmoe, attention, axk1, bailing, falcon_h1, hyper, llama, longcat,
                               mellum, xing)
from cordum_tpu.serving.backend import FeedLayout, make_ragged_program
from cordum_tpu.serving.modelspec import spec_for

PAGES, PS, SEQS, TOKENS, CONTEXT = 9, 4, 3, 8, 32

#: sha256 of the jaxpr text: PR 50's tree (llama, afmoe), PR 41's (longcat), PR 48's (axk1,
#: bailing), PR 49's (xing)
AS_IT_WAS = {
    "bailing": "e637f361c5b57e62d5a520bd12ef7af598e2600889641a3934d5fd73b91a144a",
    "llama": "37e0fe45f0a3c2265e2db836eed39e434ddf830c6e881f7039eb23fbd5bcdb56",
    "afmoe": "4f26e910d552717d6669182614a214ec2798f844b8255e71abae9edf0f4b7f30",
    "axk1": "01c33dbad4020604d77b6efa68cc8155de7b96b715e637624d7ffc7304bd98ae",
    "longcat": "2b508f8fe8fefb380ba03563ea32191691167b2510d5a4ab0ad0cf28174be0ae",
    "xing": "b8bd37f17cd4b35469c294a2634374ac73848188fa41d1f18f30ba03ac71a230",
}
CONFIGS = {"llama": llama.LlamaConfig.tiny, "afmoe": afmoe.AfmoeConfig, "axk1": axk1.Axk1Config,
           "longcat": longcat.LongcatConfig, "bailing": bailing.BailingConfig,
           "xing": xing.XingConfig}


def jaxpr_of(cfg):
    spec = spec_for(cfg)
    ring = attention.window_ring_pages(spec.window, PS, TOKENS) if spec.window else 0
    widths = (CONTEXT // PS, ring) if spec.window else (CONTEXT // PS,)
    layout = FeedLayout(TOKENS, SEQS, widths, state_rows=0 if spec.kv_positional else SEQS + 1)
    program = make_ragged_program(spec, layout, sample_logits=True, donate=False)
    params = jax.eval_shape(lambda: spec.init_params(jax.random.PRNGKey(0)))
    arenas = jax.eval_shape(lambda: tuple(spec.init_arenas(PAGES, PS, SEQS * ring + 1)) + (
        () if spec.kv_positional else tuple(spec.init_state(SEQS + 1))))
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32)
    return jax.make_jaxpr(program)(params, *arenas, feed)


def text_of(cfg) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr_of(cfg)))


def equations_under(jaxpr, scope: str, inside: bool = False):
    """Every equation traced under the named scope ``scope``, those of the
    jaxprs its equations hold (a ``jit``, a loop's body) among them: an inner
    jaxpr's name stacks start anew, so the scope is handed down."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations_under(sub, scope, here)


@pytest.mark.parametrize("family", sorted(AS_IT_WAS))
def test_the_program_traces_to_the_text_it_had(family):
    text = text_of(CONFIGS[family]())
    assert hashlib.sha256(text.encode()).hexdigest() == AS_IT_WAS[family]
    assert ("kda_step" in text) == (family == "bailing") and "ssd_step" not in text
    # the grouped products' two forms, where there is an expert layer and only there
    assert ("expert_mlp" in text) == ("ragged_dot" in text) == (family != "llama")
    # the walk's kernel of the arena's form: K and V by head, or one latent array
    assert ("head_walk" in text) == (family in ("llama", "afmoe"))
    assert ("latent_walk" in text) == (family not in ("llama", "afmoe"))
    # the hyper-connected stream's maps, in the one family whose residual is streams
    assert ("mhc_open" in text) == ("mhc_close" in text) == (family == "xing")


def test_the_new_familys_program_holds_what_the_others_lack():
    """One trace holds both forms of the recurrence (the choice is made where
    the program is lowered) and the latent walk's, the state's float32 arrays
    among its operands and results, and a feed one int32 a table row longer."""
    text = text_of(bailing.BailingConfig())
    assert "kda_step" in text and "latent_walk" in text and "platform_index" in text
    cfg = bailing.BailingConfig()
    state = f"f32[{len(cfg.kda_layers)},{SEQS + 1},{cfg.kda_dk},{cfg.n_heads},{cfg.kda_dv}]"
    assert text.count(state) >= 2
    with_state = FeedLayout(TOKENS, SEQS, (CONTEXT // PS,), state_rows=SEQS + 1)
    assert with_state.size == FeedLayout(TOKENS, SEQS, (CONTEXT // PS,)).size + SEQS + 1
    assert f"i32[{with_state.size}]" in text


def test_the_state_space_familys_program_holds_pages_and_state_in_every_layer():
    """One trace holds both forms of the mixer's recurrence (the choice is
    made where the program is lowered) under ONE jitted function that every
    layer calls, K and V arenas by head AND the state's float32 array among
    its operands and results, the by-head walk's kernel beside ``walk_jnp``,
    no latent walk and no expert layer."""
    cfg = falcon_h1.FalconH1Config()
    text = text_of(cfg)
    assert "ssd_step" in text and "platform_index" in text and "name=recurrence" in text
    assert "head_walk" in text
    assert not any(w in text for w in ("kda_step", "latent_walk", "expert_mlp", "ragged_dot"))
    state = (f"f32[{cfg.n_layers},{SEQS + 1},{cfg.ssm_state},{cfg.ssm_heads},"
             f"{cfg.ssm_head_dim}]")
    pages = f"bf16[{cfg.n_layers},{PAGES},{PS},{cfg.n_kv_heads},{cfg.head_dim}]"
    assert text.count(state) >= 2 and text.count(pages) >= 4
    with_state = FeedLayout(TOKENS, SEQS, (CONTEXT // PS,), state_rows=SEQS + 1)
    assert f"i32[{with_state.size}]" in text


def test_the_whole_expert_set_familys_program_holds_both_walks_and_both_products():
    """ISSUE 46's family is built from the parts the others run: one trace
    holds both forms of the grouped products and both walks over K and V by
    head for BOTH kinds of page (the lowering chooses: since ISSUE 47 the
    kernel walks the rings too, its second form), two kinds of arena among its
    operands and results, a ring table beside the whole-row one in its feed,
    and neither a latent walk nor a recurrence."""
    cfg = mellum.MellumConfig()
    text = text_of(cfg)
    assert all(w in text for w in ("expert_mlp", "ragged_dot", "head_walk", "platform_index"))
    assert not any(w in text for w in ("latent_walk", "kda_step", "ssd_step"))
    ring = attention.window_ring_pages(cfg.window, PS, TOKENS)
    full = f"bf16[{len(cfg.full_layers)},{PAGES},{PS},{cfg.n_kv_heads},{cfg.head_dim}]"
    rings = f"bf16[{len(cfg.window_layers)},{SEQS * ring + 1},{PS},{cfg.n_kv_heads},{cfg.head_dim}]"
    assert text.count(full) >= 4 and text.count(rings) >= 4
    # the kernel is handed each kind's arenas where they lie (``pl.ANY``): the rings' too
    assert f"Ref<any>{{{full}}}" in text and f"Ref<any>{{{rings}}}" in text
    layout = FeedLayout(TOKENS, SEQS, (CONTEXT // PS, ring))
    assert f"i32[{layout.size}]" in text
    # a rotation a kind: YaRN's factor on the full kind's cos and sin is in the trace
    assert str(round(cfg.rope_full.attention_factor, 4)) in text


@pytest.mark.parametrize("family", ["axk1", "bailing"])
def test_the_group_limited_choice_neither_sorts_nor_scatters(family):
    """ISSUE 48: under ``moe_group_select`` a group's score is two maximum
    passes and the kept set a rank by comparison: no ``sort``, no ``top_k``
    (the parent's took the two best of every group and the best groups with
    one each) and no ``scatter`` (the parent's built the mask with one); the
    one ``top_k`` a layer left is the final choice's, outside the scope."""
    cfg = CONFIGS[family]()
    assert cfg.n_group > 1
    jaxpr = jaxpr_of(cfg).jaxpr
    under = [eqn.primitive.name for eqn in equations_under(jaxpr, "moe_group_select")]
    assert {"reduce_max", "select_n", "gt", "eq", "lt"} <= set(under)
    assert not any(name in ("sort", "top_k") or name.startswith("scatter") for name in under)
    final = [eqn for eqn in equations_under(jaxpr, "moe_route") if eqn.primitive.name == "top_k"]
    assert final and all(eqn.params["k"] == cfg.top_k for eqn in final)


def test_the_hyper_connected_familys_program_holds_the_maps_round_every_sublayer():
    """ISSUE 49's family calls A.X-K1's sublayers and brackets each with the
    two maps: one trace holds each map ONCE as a jitted function that every
    sublayer calls (``name=mhc_open`` / ``name=mhc_close``: 2 x layers calls
    each), the stream float32 ``[T, n x d]`` between them, the latent walk
    and both forms of the grouped products as A.X-K1's program does, the
    router's selection bias among the operands, and one more row of counters
    behind the expert layers'.  At a width the kernels fit, both forms of each
    map are handed to the lowering."""
    cfg = xing.XingConfig()
    text = text_of(cfg)
    assert all(w in text for w in ("latent_walk", "expert_mlp", "ragged_dot", "platform_index"))
    assert not any(w in text for w in ("kda_step", "ssd_step", "head_walk", "moe_group_select"))
    calls = 2 * cfg.n_layers
    assert text.count("name=mhc_open") == text.count("name=mhc_close") == calls
    stream = f"f32[{TOKENS},{cfg.hc_mult * cfg.d_model}]"
    assert text.count(stream) >= 2 * calls and f"f32[{TOKENS},{hyper.LANES}]" in text
    assert f"f32[{cfg.n_experts}]" in text  # the selection bias
    out = TOKENS + (cfg.n_expert_layers + 1) * cfg.experts_held
    assert f"i32[{out}]" in text
    assert "tpu_custom_call" not in text and "pallas_call" in text  # the walk's and the products'
    assert not hyper.fits(cfg.hc_mult, cfg.d_model)  # 64 wide: the maps are jax.numpy's alone
    wide = str(jax.make_jaxpr(lambda x, p: hyper.mhc_open(x, p, cfg.hyper))(
        jax.ShapeDtypeStruct((TOKENS, 4 * 128), jnp.float32),
        jax.eval_shape(lambda: hyper.init_params(jax.random.PRNGKey(0), cfg.hyper, 128, jnp.bfloat16))))
    assert "platform_index" in wide and hyper.OPEN_KERNEL in wide and "pallas_call" in wide


@pytest.mark.parametrize("config", [afmoe.AfmoeConfig, longcat.LongcatConfig, mellum.MellumConfig,
                                    xing.XingConfig])
def test_a_router_of_one_group_holds_no_such_scope(config):
    cfg = config()
    assert cfg.n_group == 1
    jaxpr = jaxpr_of(cfg).jaxpr
    assert not list(equations_under(jaxpr, "moe_group_select"))
    assert any(eqn.primitive.name == "top_k" for eqn in equations_under(jaxpr, "moe_route"))
