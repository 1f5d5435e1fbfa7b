"""The packed feed of ``backend.step`` (docs/SERVING.md §The ragged entry
point): a step's integer operands travel as ONE int32 host vector, the
jitted program takes it apart by the static ``FeedLayout`` and hands the
family's program the operands it has always taken.  Held here against the
family's program called with those operands spelled out, for a family with
one kind of page and one with two and counters behind the tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cordum_tpu.models import afmoe, llama
from cordum_tpu.serving.backend import FeedLayout, ServingBackend, StepEntry

PS = 8
S_ROWS, T_BUF = 5, 20


def llama_cfg():
    return llama.LlamaConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                             d_ff=128, max_seq_len=128, dtype=jnp.float32)


def afmoe_cfg():
    return afmoe.AfmoeConfig(
        vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        d_expert=32, n_layers=3, n_dense_layers=1,
        layer_types=(afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL), window=32, n_experts=16,
        first_expert=0, experts_held=16, top_k=2, max_seq_len=128, dtype=jnp.float32)


FAMILIES = {"llama": llama_cfg, "afmoe": afmoe_cfg}


@pytest.fixture(params=sorted(FAMILIES))
def backend(request):
    cfg = FAMILIES[request.param]()
    be = ServingBackend(cfg, num_pages=96, page_size=PS, max_seqs=S_ROWS,
                        max_batch_tokens=T_BUF, seed=3)
    be._ensure()
    return be


def rows_of(be, n):
    """``(pages, ring)`` of row i: pages of its own in each kind's pool."""
    per, ring = be.pages_per_seq, be.ring_pages
    return [(list(range(1 + i * per, 1 + (i + 1) * per)),
             list(range(1 + i * ring, 1 + (i + 1) * ring))) for i in range(n)]


def entry(row, tokens, start, **kw):
    return StepEntry(tokens=list(tokens), start=start, pages=row[0], window_pages=row[1], **kw)


def spelled_out(be, entries):
    """The family's operands for ``entries`` as separate arrays, built here
    without the layout: ``(tokens, positions, tables, token_seq, out_idx)``."""
    t, s = be.max_batch_tokens, be.max_seqs
    tokens, positions = np.zeros((t,), np.int32), np.zeros((t,), np.int32)
    token_seq, out_idx = np.full((t,), s, np.int32), np.zeros((s,), np.int32)
    widths = [be.pages_per_seq] + ([be.ring_pages] if be.window else [])
    tables = [np.zeros((s + 1, w), np.int32) for w in widths]
    at = 0
    for i, e in enumerate(entries):
        n = len(e.tokens)
        tokens[at:at + n] = e.tokens
        positions[at:at + n] = range(e.start, e.start + n)
        token_seq[at:at + n] = i
        for tb, pages in zip(tables, (e.pages, e.window_pages)):
            tb[i, :len(pages)] = pages
        out_idx[i] = at + n - 1
        at += n
    return tokens, positions, tables, token_seq, out_idx


def test_a_mixed_step_through_the_packed_feed_equals_the_spelled_out_program(backend):
    be = backend
    rows = rows_of(be, 4)
    # two rows hold a prefix already, so the mixed step's decode and draft
    # rows attend to pages an earlier step wrote
    be.step([entry(rows[0], range(10, 19), 0, phase="prefill"),
             entry(rows[1], range(30, 36), 0, phase="prefill")])
    before = [np.array(a) for a in be._arenas]
    mixed = [
        entry(rows[0], [7], 9),  # a decode row
        entry(rows[2], range(40, 47), 0, sample=False, phase="prefill"),  # a chunk, prompt not done
        entry(rows[1], [8, 9, 10], 6, draft=2),  # a draft row: one vote a fed position
        entry(rows[3], [5, 6], 0, phase="prefill"),  # a chunk that completes its prompt
    ]  # 13 of 20 slots and 4 of 5 rows: the rest is padding
    got = be.step(mixed)

    tokens, positions, tables, token_seq, out_idx = spelled_out(be, mixed)
    out, *arenas = jax.jit(be.spec.program(True))(
        be._params, *(jnp.asarray(a) for a in before), jnp.asarray(tokens),
        jnp.asarray(positions), *(jnp.asarray(tb) for tb in tables), jnp.asarray(token_seq),
        jnp.asarray(out_idx))
    out = np.asarray(out)
    assert got == [int(out[0]), None, [int(x) for x in out[8:11]], int(out[12])]
    for mine, theirs in zip(be._arenas, arenas):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    if be.spec.aux_shape:
        np.testing.assert_array_equal(
            be.last_aux, out[be.max_batch_tokens:].reshape(be.spec.aux_shape))
        assert (be.last_counters, be.last_attrs) == be.spec.count_aux(be.last_aux, 13, be.kernels)
        assert be.last_counters["moe_assignments_here"] > 0
        assert be.last_attrs["moe_here"] == str(be.last_counters["moe_assignments_here"])
    else:
        assert out.shape == (be.max_batch_tokens,) and be.last_aux is None


def test_step_hands_the_program_one_numpy_vector_and_compiles_once(backend):
    be = backend
    program, calls = be._ragged_jit, []

    def tapped(*operands):
        calls.append(operands)
        return program(*operands)

    be._ragged_jit = tapped
    rows = rows_of(be, 3)
    be.step([entry(rows[0], range(1, 12), 0, phase="prefill")])
    assert be.last_step_compiled
    be.step([entry(rows[0], [3], 11), entry(rows[1], range(20, 24), 0, phase="prefill"),
             entry(rows[2], [4, 5], 0, draft=1)])
    assert not be.last_step_compiled
    for operands in calls:
        assert len(operands) == 1 + be.spec.n_arenas + 1
        feed = operands[-1]
        assert type(feed) is np.ndarray and feed.dtype == np.int32
        assert feed.shape == (be.feed_layout.size,)
    assert calls[0][-1] is not calls[1][-1]  # a transfer may outlive the call
    assert program._cache_size() == 1 and be.compiled_programs() == 1


@pytest.mark.parametrize("widths", [(16,), (1024, 261)])
def test_the_layout_covers_the_vector_once_with_views(widths):
    layout = FeedLayout(tokens=64, seqs=16, table_widths=widths)
    assert layout.size == 3 * 64 + 16 + 17 * sum(widths)
    feed = np.zeros((layout.size,), np.int32)
    tokens, positions, token_seq, out_idx, tables = layout.split(feed)
    parts = [tokens, positions, token_seq, out_idx, *tables]
    assert [p.shape for p in parts] == [(64,), (64,), (64,), (16,)] + [(17, w) for w in widths]
    for n, part in enumerate(parts, start=1):
        assert np.shares_memory(part, feed)
        part += n  # each slot written once: no two views overlap, none is left out
    assert np.array_equal(feed, np.concatenate([np.full(p.size, n, np.int32)
                                                for n, p in enumerate(parts, start=1)]))
    # the program's side of the same layout: a traced operand splits alike
    traced = jax.jit(lambda f: layout.split(f))(jnp.asarray(feed))
    for part, other in zip(parts, [*traced[:4], *traced[4]]):
        np.testing.assert_array_equal(part, np.asarray(other))
