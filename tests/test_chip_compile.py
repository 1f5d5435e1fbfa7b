"""Compile the main path's jitted programs for a DESCRIBED TPU v5e, at the
sizes ``chip_smoke.py`` runs on the chip (its ``REAL`` table), and hold each
to 16 GB per device from ``memory_analysis()``.

Nothing runs and no chip is attached: the TPU compiler is installed beside
the CPU backend and compiles for a topology that is only described.  A
compile that passes is not a chip run; what it catches is what the chip's
compiler would refuse — a program that does not fit, a layout it cannot
partition.  The topology is described inside a module-scoped fixture (never
at import: one process at a time may load the TPU library, and every xdist
worker imports every test file), and all of these live in this one file so
that one worker loads the library once.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke
from cordum_tpu.models import attention, embedder, latent_walk, llama
from cordum_tpu.serving.backend import FeedLayout, make_ragged_program
from cordum_tpu.worker.handlers import make_matmul_program

SZ = chip_smoke.REAL
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip, so the cache is off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means "cannot run here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def shaped(tree, sharding):
    """Shapes of ``tree`` placed by ``sharding`` (one sharding, or a pytree
    of them matching ``tree``)."""
    if not isinstance(sharding, (dict, list, tuple)):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sharding)


def device_bytes(compiled) -> int:
    """What one device holds while the program runs: arguments, outputs not
    aliased onto them, temporaries and the code."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def holds_walk_kernel(text: str) -> bool:
    """Whether a program's text (lowered or compiled) calls the latent
    walk's kernel: a TPU custom call under the kernel's name."""
    return any("tpu_custom_call" in line and latent_walk.KERNEL_NAME in line
               for line in text.splitlines())


def holds_head_kernel(text: str) -> bool:
    """Whether a program's text calls the by-head walk's kernel."""
    from cordum_tpu.models import head_walk

    return any("tpu_custom_call" in line and head_walk.KERNEL_NAME in line
               for line in text.splitlines())


def holds_expert_kernel(text: str) -> bool:
    """Whether a program's text calls the grouped expert products' kernel."""
    from cordum_tpu.models import expert_mlp

    return any("tpu_custom_call" in line and expert_mlp.KERNEL_NAME in line
               for line in text.splitlines())


def smoke_cfg(**kw):
    return dataclasses.replace(
        llama.LlamaConfig.llama3_8b(), n_layers=SZ["n_layers"],
        max_seq_len=SZ["max_seq_len"], **kw)


def serving_shapes(cfg, num_pages, max_seq_len, params_sharding, arena_sharding, small):
    """Argument shapes of the ragged program, as the backend builds them."""
    params = shaped(
        jax.eval_shape(lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0)),
        params_sharding)
    arena = jax.ShapeDtypeStruct(
        (cfg.n_layers, num_pages, SZ["page_size"], cfg.n_kv_heads, cfg.head_dim),
        cfg.dtype, sharding=arena_sharding)
    s_rows = SZ["max_sessions"]
    layout = FeedLayout(s_rows + SZ["prefill_budget"], s_rows,
                        (-(-max_seq_len // SZ["page_size"]),))
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=small)
    return params, arena, layout, feed


def test_widths_are_the_published_ones():
    cfg = smoke_cfg()
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (4096, 32, 8, 128, 14336, 128256)
    assert jnp.dtype(cfg.dtype) == jnp.bfloat16


@pytest.mark.parametrize("sample_logits", [True, False])
def test_ragged_step_fits_one_chip(one_chip, sample_logits):
    cfg = smoke_cfg()
    params, arena, layout, feed = serving_shapes(
        cfg, SZ["pages"], cfg.max_seq_len, one_chip, one_chip, one_chip)
    program = make_ragged_program(cfg, layout, sample_logits=sample_logits, donate=True)
    compiled = program.lower(params, arena, arena, feed).compile()
    ma = compiled.memory_analysis()
    arena_bytes = arena.size * arena.dtype.itemsize
    # donation is real: both arenas alias onto the outputs
    assert ma.alias_size_in_bytes >= 2 * arena_bytes
    # plus ONE extra arena copy: the page scatter/copy programs do not
    # donate, so an import or a copy-on-write holds a second arena briefly
    assert device_bytes(compiled) + arena_bytes <= 0.95 * HBM_BYTES


@pytest.mark.parametrize("name", ["_gather_page", "_scatter_page", "_copy_page"])
def test_page_programs_compile_on_the_real_arena(one_chip, name):
    cfg = smoke_cfg()
    shape = (cfg.n_layers, SZ["pages"], SZ["page_size"], cfg.n_kv_heads, cfg.head_dim)
    arena = jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one_chip)
    pid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    block = jax.ShapeDtypeStruct(shape[:1] + shape[2:], cfg.dtype, sharding=one_chip)
    args = {"_gather_page": (arena, pid), "_scatter_page": (arena, pid, block),
            "_copy_page": (arena, pid, pid)}[name]
    compiled = getattr(attention, name).lower(*args).compile()
    arena_bytes = arena.size * arena.dtype.itemsize
    ma = compiled.memory_analysis()
    if name != "_gather_page":
        # not donated: the output is a whole second arena
        assert ma.alias_size_in_bytes == 0
        assert ma.output_size_in_bytes >= arena_bytes
    assert device_bytes(compiled) <= HBM_BYTES


def test_the_latent_step_fits_one_chip_and_keeps_its_arena_in_place(one_chip):
    """The axk1 cell's ragged program at the benchmark's sizes (``benchmarks/
    configs/a.x-k1-ep16.json``): weights and the latent arena fit, donation
    is real, and the arena is neither copied nor laid out anew.  A latent
    arena 576 wide (a shape that needs padding to the 128-lane tile) was laid
    out pages-minor by the compiler and copied whole twice a step: 18.28 of
    15.75 GB; the arena is 640 wide for that reason (``Axk1Config.
    latent_width``).  The in-place page copy behind copy-on-write holds no
    second arena."""
    from benchmarks.families import axk1 as fam
    from benchmarks.harness import cells

    doc = dict(cells.load_config("a.x-k1-ep16"))
    cfg, pool = fam.program_config(doc), doc["pool"]
    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip),
        fam.param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple))
    arena = jax.ShapeDtypeStruct(
        (cfg.n_layers, pool["pages"], pool["page_size"], cfg.latent_width), cfg.dtype,
        sharding=one_chip)
    assert cfg.latent_width == 640 and arena.shape[1] * pool["page_size"] == 16 * cfg.max_seq_len
    layout = FeedLayout(pool["max_sessions"] + pool["prefill_budget"], pool["max_sessions"],
                        (cfg.max_seq_len // pool["page_size"],))
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=one_chip)
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=True)
    compiled = program.lower(params, arena, feed).compile()
    ma = compiled.memory_analysis()
    arena_bytes = arena.size * arena.dtype.itemsize
    assert ma.alias_size_in_bytes >= arena_bytes
    assert ma.temp_size_in_bytes < 0.5e9  # no copy of the 4 GB arena among the temporaries
    assert 12.0e9 < device_bytes(compiled) <= 0.85 * HBM_BYTES
    assert holds_walk_kernel(compiled.as_text())  # the walk is the kernel, with the arena in place
    pid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    copied = attention._copy_page_in_place.lower(arena, pid, pid).compile().memory_analysis()
    assert copied.alias_size_in_bytes >= arena_bytes and copied.temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("config", ["mistral-7b-v0.3", "internlm2-1.8b",
                                    "trinity-large-preview-ep8"])
def test_a_by_head_program_holds_the_by_head_walks_kernel(config):
    """K and V by head with no window walk their pages with ``head_walk``'s
    kernel where the program is lowered for the TPU (ISSUE 44), never with
    the latent form's: the benchmark's three by-head programs, LOWERED at
    their cells' shapes (nothing compiled); Trinity's holds it for its full
    layers and, since ISSUE 47, in its second form for its rings.  Lowered for the CPU
    they call no kernel; the latent program holds its own kernel on the TPU
    and none on the CPU."""
    from benchmarks.harness import cells
    from cordum_tpu.serving.backend import ServingBackend

    def lowered(name, platform):
        doc = dict(cells.load_config(name))
        fam = __import__(f"benchmarks.families.{doc['family']}", fromlist=["x"])
        cfg, pool = fam.program_config(doc), doc["pool"]
        be = ServingBackend(cfg, num_pages=pool["pages"], page_size=pool["page_size"],
                            max_seqs=pool["max_sessions"],
                            max_batch_tokens=pool["max_sessions"] + pool["prefill_budget"])
        params = jax.eval_shape(be.spec.init_params, jax.random.PRNGKey(0))
        arenas = jax.eval_shape(lambda: tuple(be.spec.init_arenas(
            be.num_pages, be.page_size, be.num_window_pages)))
        feed = jax.ShapeDtypeStruct((be.feed_layout.size,), jnp.int32)
        program = make_ragged_program(be.spec, be.feed_layout, sample_logits=True, donate=False)
        return program.trace(params, *arenas, feed).lower(
            lowering_platforms=(platform,)).as_text()

    text = lowered(config, "tpu")
    assert holds_head_kernel(text) and not holds_walk_kernel(text)
    assert not holds_head_kernel(lowered(config, "cpu"))
    # the grouped products' kernel: where there is an expert layer, for the TPU
    assert holds_expert_kernel(text) == (config == "trinity-large-preview-ep8")
    if config == "trinity-large-preview-ep8":  # once: the rule's other side
        latent = lowered("a.x-k1-ep16", "tpu")
        assert holds_walk_kernel(latent) and holds_expert_kernel(latent)
        assert not holds_head_kernel(latent)
        on_cpu = lowered("a.x-k1-ep16", "cpu")
        assert not holds_walk_kernel(on_cpu) and not holds_expert_kernel(on_cpu)


@pytest.mark.parametrize("config,kvh,rep,window", [
    ("trinity-large-preview-ep8", 8, 6, 4096), ("mellum2-12b-a2.5b-pp", 4, 8, 1024)])
def test_the_ring_form_of_the_by_head_kernel_compiles_at_the_window_cells_shapes(
        one_chip, config, kvh, rep, window):
    """ISSUE 47: ``head_walk``'s second, static form (a first block a tile,
    the ring's modulus on the scalar core where a page's copy is started, the
    window's lower bound in the mask) COMPILED for the described v5e at the
    two window cells' shapes, one group of tiles over the window kind's arenas
    and ring tables as the cell's backend lays them out, inside the kernels'
    VMEM budget; the rings are no whole number of blocks wide."""
    from benchmarks.harness import cells
    from cordum_tpu.models import head_walk
    from cordum_tpu.serving.backend import ServingBackend

    doc = dict(cells.load_config(config))
    fam = __import__(f"benchmarks.families.{doc['family']}", fromlist=["x"])
    cfg, pool = fam.program_config(doc), doc["pool"]
    be = ServingBackend(cfg, num_pages=pool["pages"], page_size=pool["page_size"],
                        max_seqs=pool["max_sessions"],
                        max_batch_tokens=pool["max_sessions"] + pool["prefill_budget"])
    assert (cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, be.window) == (kvh, rep, window)
    assert be.kernels == {} and be.spec.kernels("tpu", 1)["ring"] == head_walk.KERNEL_NAME
    k_arena = jax.eval_shape(lambda: be.spec.init_arenas(
        be.num_pages, be.page_size, be.num_window_pages))[2]
    bt = be._block_tokens[1]
    g, w = attention.ATTN_GROUP_TILES, attention.attn_tile_slots(rep)
    tiles = attention.attn_tiles(be.max_batch_tokens, be.max_seqs, w)
    assert be.ring_pages % (bt // be.page_size) and bt == attention.ATTN_BLOCK_TOKENS
    assert head_walk.vmem_bytes(g, kvh, w * rep, cfg.head_dim, bt, 2) <= head_walk.VMEM_BUDGET_BYTES

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(lambda q, pos, k, v, row, tab, trips, runs, first: head_walk.walk_group(
        q, pos, k, v, row, tab, trips, runs, g, block_pages=bt // be.page_size,
        scale=cfg.head_dim ** -0.5, window=window, first_blocks=first)).lower(
        shape((tiles, kvh, w * rep, cfg.head_dim), k_arena.dtype), shape((g, w)),
        shape(k_arena.shape, k_arena.dtype), shape(k_arena.shape, k_arena.dtype), shape(()),
        shape((g, be.ring_pages)), shape((g,)), shape((g, 3)), shape((g,))).compile()
    assert holds_head_kernel(compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6  # no copy of an arena


@pytest.mark.parametrize("rows,d,fe,held", [
    (1024, 2560, 768, 128), (512, 7168, 2048, 12), (1536, 6144, 2048, 16), (256, 3072, 3072, 32),
    (2048, 2304, 896, 64)],
    ids=["ling", "axk1", "longcat", "trinity", "mellum"])
def test_the_expert_kernel_compiles_at_the_sparse_cells_shapes(one_chip, rows, d, fe, held):
    """``expert_mlp`` alone at each sparse cell's committed shapes (T x top_k
    rows, the experts held): Mosaic takes the blocks ``block_width`` derives,
    the 16-row pieces' copies and the kernel's VMEM under its stated budget."""
    from cordum_tpu.models import expert_mlp

    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)  # noqa: E731
    assert expert_mlp.holds_kernel("tpu", d, fe, 2)
    compiled = jax.jit(expert_mlp.expert_mlp).lower(
        shape((rows, d), jnp.bfloat16), shape((held, d, fe), jnp.bfloat16),
        shape((held, d, fe), jnp.bfloat16), shape((held, fe, d), jnp.bfloat16),
        shape((held,), jnp.int32)).compile()
    assert holds_expert_kernel(compiled.as_text())


def test_the_shortcut_connected_step_fits_one_chip_and_keeps_its_arena_in_place(one_chip):
    """The longcat cell's ragged program at the benchmark's sizes (``benchmarks/
    configs/longcat-flash-chat-ep32.json``: published widths, four layers of
    two latent-attention sublayers, 16 of 512 real experts held, 32 sessions
    x 8192 positions): 10.35 GB of weights and the 2.68 GB arena of EIGHT rows
    (a row a sublayer) fit, donation is real, and the arena is neither copied
    nor laid out anew (PERF.md section 4 has the reading)."""
    from benchmarks.families import longcat as fam
    from benchmarks.harness import cells

    doc = dict(cells.load_config("longcat-flash-chat-ep32"))
    cfg, pool = fam.program_config(doc), doc["pool"]
    shapes = fam.param_shapes(doc)
    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    for layer in params["layers"]:  # the selection bias is float32
        layer["router_bias"] = jax.ShapeDtypeStruct(
            layer["router_bias"].shape, jnp.float32, sharding=one_chip)
    arena = jax.ShapeDtypeStruct(
        (cfg.n_sublayers, pool["pages"], pool["page_size"], cfg.latent_width), cfg.dtype,
        sharding=one_chip)
    assert (cfg.n_sublayers, cfg.latent_width) == (8, 640)
    assert arena.shape[1] * pool["page_size"] == pool["max_sessions"] * cfg.max_seq_len
    layout = FeedLayout(pool["max_sessions"] + pool["prefill_budget"], pool["max_sessions"],
                        (cfg.max_seq_len // pool["page_size"],))
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=one_chip)
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=True)
    compiled = program.lower(params, arena, feed).compile()
    ma = compiled.memory_analysis()
    arena_bytes = arena.size * arena.dtype.itemsize
    assert ma.alias_size_in_bytes >= arena_bytes
    assert ma.temp_size_in_bytes < 0.5e9  # no copy of the 2.7 GB arena among the temporaries
    assert 13.0e9 < device_bytes(compiled) <= 0.9 * HBM_BYTES
    assert holds_walk_kernel(compiled.as_text())  # one kernel for the eight sublayers' walks
    print(f"longcat step: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, code {ma.generated_code_size_in_bytes / 1e6:.1f} MB")


def test_the_hybrid_step_fits_one_chip_and_keeps_state_and_pages_in_place(one_chip):
    """The bailing cell's ragged program at the benchmark's sizes (``benchmarks/
    configs/ling-3.0-flash-ep4.json``: published widths, six KDA layers and one
    latent layer, 128 of 512 experts held, 64 sessions): 10.34 GB of weights,
    the 2.01 GB latent arena and 0.85 GB of state slots fit, donation is real
    for all three cache arrays (none copied, none laid out anew), and the
    program lowered for the TPU holds both kernels: ``latent_walk`` for the
    one latent layer, ``kda_step`` once a KDA layer (PERF.md section 4)."""
    from benchmarks.families import bailing as fam
    from benchmarks.harness import cells
    from cordum_tpu.models import kda

    doc = dict(cells.load_config("ling-3.0-flash-ep4"))
    cfg, pool = fam.program_config(doc), doc["pool"]

    def leaf(path, shape):
        dtype = jnp.float32 if path[-1].key in fam.FLOAT32 else jnp.bfloat16
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        leaf, fam.param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple))
    seqs = pool["max_sessions"]
    spec = cfg.serving_spec()
    caches = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.eval_shape(
        lambda: spec.init_arenas(pool["pages"], pool["page_size"], 0) + spec.init_state(seqs + 1))]
    assert [c.shape for c in caches] == [(1, 98304, 16, 640), (6, 65, 128, 32, 128),
                                         (6, 65, 3, 12288)]
    assert caches[1].dtype == jnp.float32
    layout = FeedLayout(seqs + pool["prefill_budget"], seqs,
                        (cfg.max_seq_len // pool["page_size"],), state_rows=seqs + 1)
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=one_chip)
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=True)
    compiled = program.lower(params, *caches, feed).compile()
    ma = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize for c in caches)
    assert ma.alias_size_in_bytes >= cache_bytes > 2.8e9
    assert ma.temp_size_in_bytes < 0.5e9  # no copy of the state or the arena among the temporaries
    assert 12.5e9 < device_bytes(compiled) <= 0.9 * HBM_BYTES
    text = compiled.as_text()
    assert holds_walk_kernel(text) and kda.KERNEL_NAME in text and holds_expert_kernel(text)
    print(f"bailing step: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, code {ma.generated_code_size_in_bytes / 1e6:.1f} MB")


def test_the_state_space_step_fits_one_chip_and_keeps_state_and_pages_in_place(one_chip):
    """The falcon_h1 cell's ragged program at the benchmark's sizes (``benchmarks/
    configs/falcon-h1-34b-pp8.json``: published widths, 9 layers each with a
    mixer AND attention, an eighth of the vocabulary, 80 sessions): 8.41 GB of
    weights, 2.26 GB of K and V pages and 3.08 GB of state slots fit, donation
    is real for all four cache arrays, and the program lowered for the TPU
    holds ``ssd_step`` (ONE kernel body, called a layer: the recurrence is
    jitted with the layer a traced operand), the by-head walk's kernel
    (``head_walk``, ISSUE 44: both arenas stay in place under it) and no
    other kernel."""
    from benchmarks.families import falcon_h1 as fam
    from benchmarks.harness import cells
    from cordum_tpu.models import ssd

    doc = dict(cells.load_config("falcon-h1-34b-pp8"))
    cfg, pool = fam.program_config(doc), doc["pool"]

    def leaf(path, shape):
        dtype = jnp.float32 if path[-1].key in fam.FLOAT32 else jnp.bfloat16
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        leaf, fam.param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple))
    seqs = pool["max_sessions"]
    spec = cfg.serving_spec()
    caches = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.eval_shape(
        lambda: tuple(spec.init_arenas(pool["pages"], pool["page_size"], 0))
        + spec.init_state(seqs + 1))]
    assert [c.shape for c in caches] == [(9, 7680, 16, 4, 128)] * 2 + [
        (9, 81, 256, 32, 128), (9, 81, 3, 5120)]
    assert caches[2].dtype == jnp.float32
    layout = FeedLayout(seqs + pool["prefill_budget"], seqs,
                        (cfg.max_seq_len // pool["page_size"],), state_rows=seqs + 1)
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=one_chip)
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=True)
    compiled = program.lower(params, *caches, feed).compile()
    ma = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize for c in caches)
    assert ma.alias_size_in_bytes >= cache_bytes > 5.3e9
    assert ma.temp_size_in_bytes < 0.5e9  # no copy of the state or of an arena among the temporaries
    assert 13.5e9 < device_bytes(compiled) <= 0.9 * HBM_BYTES
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and ssd.KERNEL_NAME in ln]
    assert calls and not holds_walk_kernel(text) and not holds_expert_kernel(text)
    assert holds_head_kernel(text)
    print(f"falcon_h1 step: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, code {ma.generated_code_size_in_bytes / 1e6:.1f} MB, "
          f"{len(calls)} ssd_step call lines")


def test_the_whole_expert_set_step_fits_one_chip_and_keeps_both_kinds_of_page_in_place(one_chip):
    """The mellum cell's ragged program at the benchmark's sizes (``benchmarks/
    configs/mellum2-12b-a2.5b-pp.json``: published widths, 8 layers of 64
    experts each held whole, the whole vocabulary, 32 sessions over a 20480
    context): 7.59 GB of weights, 2.68 GB of whole-row pages for the two full
    layers and 0.51 GB of rings for the six window layers fit, donation is
    real for all four arenas, and the program lowered for the TPU holds the
    grouped products' kernel (``expert_mlp``) AND the by-head walk's
    (``head_walk``, for the full kind and, since ISSUE 47, for the rings)
    and no latent walk."""
    from benchmarks.families import mellum as fam
    from benchmarks.harness import cells
    from cordum_tpu.serving.backend import ServingBackend

    doc = dict(cells.load_config("mellum2-12b-a2.5b-pp"))
    cfg, pool = fam.program_config(doc), doc["pool"]
    params = jax.tree.map(lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip),
                          fam.param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple))
    be = ServingBackend(cfg, num_pages=pool["pages"], page_size=pool["page_size"],
                        max_seqs=pool["max_sessions"],
                        max_batch_tokens=pool["max_sessions"] + pool["prefill_budget"])
    assert (be.ring_pages, be.num_window_pages, be.pages_per_seq) == (81, 2593, 1280)
    arenas = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.eval_shape(
        lambda: tuple(be.spec.init_arenas(be.num_pages, be.page_size, be.num_window_pages)))]
    assert [a.shape for a in arenas] == [(2, 40960, 16, 4, 128)] * 2 + [(6, 2593, 16, 4, 128)] * 2
    feed = jax.ShapeDtypeStruct((be.feed_layout.size,), jnp.int32, sharding=one_chip)
    program = make_ragged_program(be.spec, be.feed_layout, sample_logits=True, donate=True)
    compiled = program.lower(params, *arenas, feed).compile()
    ma = compiled.memory_analysis()
    arena_bytes = sum(a.size * a.dtype.itemsize for a in arenas)
    assert ma.alias_size_in_bytes >= arena_bytes > 3.1e9
    assert ma.temp_size_in_bytes < 0.5e9  # no copy of an arena among the temporaries
    assert 10.7e9 < device_bytes(compiled) <= 0.8 * HBM_BYTES
    text = compiled.as_text()
    assert holds_expert_kernel(text) and holds_head_kernel(text) and not holds_walk_kernel(text)
    print(f"mellum step: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, code {ma.generated_code_size_in_bytes / 1e6:.1f} MB")


@pytest.mark.parametrize("slots,y_dtype", [(240, jnp.bfloat16), (240, jnp.float32),
                                          (112, jnp.float32), (136, jnp.bfloat16)],
                         ids=["cell-attention", "cell-experts", "budget-96", "not-whole-tiles"])
def test_the_maps_kernels_compile_at_the_hyper_connected_cells_shapes(one_chip, slots, y_dtype):
    """``mhc_open`` and ``mhc_close`` alone at the xing cell's committed shapes
    (240 buffer slots of four streams 3584 wide; a sublayer's output in
    bfloat16 from the attention, float32 from the expert layer), at the
    smallest budget its sweep may choose (112 slots) and on a buffer that is
    not whole tiles (136), each brought to whole tiles of the open by the
    wrappers (``hyper.padded_slots``: no partial tile reaches Mosaic, which
    hung the chip inside a step, PERF.md section 6, PR 49): Mosaic takes the
    24-row bfloat16 ``phi`` against a tile contracted on the minor axis, the
    two 128 x 128 transposes and the VMEM the module states."""
    from cordum_tpu.models import hyper

    hc, width = hyper.Hyper(), 3584
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)  # noqa: E731
    assert hyper.holds_kernel("tpu", hyper.fits(hc.n, width))
    opened = jax.jit(lambda x, phi, a, b: hyper.open_kernel(x, phi, a, b, hc)).lower(
        shape((slots, hc.n * width), jnp.float32), shape((hc.rows, hc.n * width), jnp.bfloat16),
        shape((3,), jnp.float32), shape((hc.rows,), jnp.float32)).compile()
    closed = jax.jit(lambda x, y, m: hyper.close_kernel(x, y, m, hc)).lower(
        shape((slots, hc.n * width), jnp.float32), shape((slots, width), y_dtype),
        shape((slots, hyper.LANES), jnp.float32)).compile()
    for compiled, name in ((opened, hyper.OPEN_KERNEL), (closed, hyper.CLOSE_KERNEL)):
        assert any("tpu_custom_call" in line and name in line
                   for line in compiled.as_text().splitlines())


def test_the_hyper_connected_step_fits_one_chip_and_keeps_its_arena_in_place(one_chip):
    """The xing cell's ragged program at the benchmark's sizes (``benchmarks/
    configs/xing4.0-29b-a4b-pp.json``: published widths, one dense and five
    expert layers of 64 experts each held whole, the whole vocabulary, 16
    sessions over a 16384 context): 9.59 GB of weights and the 2.01 GB latent
    arena fit, donation is real, the arena is neither copied nor laid out
    anew, and the program lowered for the TPU holds all four kernels: the
    latent walk, the grouped products, and both maps of every sublayer."""
    from benchmarks.families import xing as fam
    from benchmarks.harness import cells
    from cordum_tpu.models import hyper

    doc = dict(cells.load_config("xing4.0-29b-a4b-pp"))
    cfg, pool = fam.program_config(doc), doc["pool"]
    params = jax.tree_util.tree_map_with_path(
        lambda path, shape: jax.ShapeDtypeStruct(
            shape, jnp.float32 if path[-1].key in fam.FLOAT32 else jnp.bfloat16, sharding=one_chip),
        fam.param_shapes(doc), is_leaf=lambda x: isinstance(x, tuple))
    arena = jax.ShapeDtypeStruct(
        (cfg.n_layers, pool["pages"], pool["page_size"], cfg.latent_width), cfg.dtype,
        sharding=one_chip)
    assert cfg.latent_width == 640 and arena.shape[1] * pool["page_size"] == 16 * cfg.max_seq_len
    layout = FeedLayout(pool["max_sessions"] + pool["prefill_budget"], pool["max_sessions"],
                        (cfg.max_seq_len // pool["page_size"],))
    feed = jax.ShapeDtypeStruct((layout.size,), jnp.int32, sharding=one_chip)
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=True)
    compiled = program.lower(params, arena, feed).compile()
    ma = compiled.memory_analysis()
    arena_bytes = arena.size * arena.dtype.itemsize
    assert ma.alias_size_in_bytes >= arena_bytes > 2.0e9
    assert ma.temp_size_in_bytes < 0.5e9  # no copy of the arena among the temporaries
    assert 11.5e9 < device_bytes(compiled) <= 0.8 * HBM_BYTES
    text = compiled.as_text()
    assert holds_walk_kernel(text) and holds_expert_kernel(text) and not holds_head_kernel(text)
    calls = [line for line in text.splitlines() if "custom_call_target=\"tpu_custom_call\"" in line]
    for name in (hyper.OPEN_KERNEL, hyper.CLOSE_KERNEL):
        assert sum(f"%{name}" in line.split("=")[0] for line in calls) == cfg.n_sublayers
    print(f"xing step: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, code {ma.generated_code_size_in_bytes / 1e6:.1f} MB")


def test_reference_forward_fits_beside_the_serving_state(one_chip):
    cfg = smoke_cfg()
    params, arena, _, _ = serving_shapes(
        cfg, SZ["pages"], cfg.max_seq_len, one_chip, one_chip, one_chip)
    tokens = jax.ShapeDtypeStruct((1, SZ["ref_len"]), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: llama.forward(p, t, cfg)).lower(params, tokens).compile()
    # the reference runs while both arenas are still resident
    arena_bytes = arena.size * arena.dtype.itemsize
    assert device_bytes(compiled) + 2 * arena_bytes <= 0.95 * HBM_BYTES


def test_embedder_largest_micro_batch_compiles(one_chip):
    cfg = embedder.EmbedderConfig()
    params = shaped(
        jax.eval_shape(lambda k: embedder.init_params(k, cfg), jax.random.PRNGKey(0)),
        one_chip)
    rows = 32  # the micro-batcher's default row limit, its widest batch bucket
    ids = jax.ShapeDtypeStruct((rows, cfg.max_len), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows, cfg.max_len), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, i, m: embedder.forward(p, i, m, cfg)).lower(params, ids, mask).compile()
    assert device_bytes(compiled) <= HBM_BYTES


def test_matmul_op_compiles(one_chip):
    n = SZ["matmul_n"]
    x = jax.ShapeDtypeStruct((2, n, n), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((n, n), jnp.bfloat16, sharding=one_chip)
    compiled = make_matmul_program(1).lower(x, w, w).compile()
    assert device_bytes(compiled) <= HBM_BYTES


def test_sharded_ragged_step_full_depth_on_four_chips(topo):
    """The --chips 4 program: all 32 layers, tensor-parallel over a 2x2 v5e
    host.  No single chip holds the model; a quarter each must fit."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.llama3_8b(), max_seq_len=SZ["tp_max_seq_len"])
    assert cfg.n_layers == 32
    import numpy as np

    from cordum_tpu.parallel.mesh import AXIS_DP, AXIS_TP

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), (AXIS_DP, AXIS_TP))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), llama.param_specs(cfg))
    params, arena, layout, feed = serving_shapes(
        cfg, SZ["tp_pages"], cfg.max_seq_len, pshard,
        NamedSharding(mesh, attention.KV_ARENA_SPEC), NamedSharding(mesh, P()))
    program = make_ragged_program(cfg, layout, sample_logits=True, donate=True)
    compiled = program.lower(params, arena, arena, feed).compile()
    weight_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weight_bytes > HBM_BYTES * 0.9  # one chip could not hold it
    per_device = device_bytes(compiled)
    assert per_device <= 0.95 * HBM_BYTES
    assert per_device < 0.5 * weight_bytes  # no device holds the whole model
    text = compiled.as_text()
    # a program partitioned over a mesh keeps the ``jax.numpy`` walk: a
    # Pallas call is one device's
    assert not holds_head_kernel(text)
    # row-parallel wo and w_down end in a sum over tp, in every layer
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") >= 2 * cfg.n_layers

    # weights are CREATED sharded: the init program's output on each device
    # is a quarter of the model, never the whole
    init = jax.jit(lambda k: llama.init_params(k, cfg), out_shardings=pshard)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
    init_ma = init.lower(key).compile().memory_analysis()
    assert init_ma.output_size_in_bytes < 0.3 * weight_bytes
    assert init_ma.output_size_in_bytes + init_ma.temp_size_in_bytes <= 0.95 * HBM_BYTES
