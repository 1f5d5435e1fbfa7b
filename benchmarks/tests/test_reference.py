"""The plain reference against the program's ``llama.forward`` at tiny widths
in float32; the sampling of requests; the verdict; and the control: the
reference computed through int8 comes out NOT correct against the cells'
limits at a size this test can hold."""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cells, reference
from benchmarks.families import llama as fam
from benchmarks.run import TINY



def tiny_doc(**over):
    return {**cells.load_config("mistral-7b-v0.3"), **TINY, **over}


def test_reference_equals_llama_forward_in_float32():
    from cordum_tpu.models import llama

    doc = tiny_doc()
    params = fam.make_params(doc, seed=2 ** 31 + 9)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    cfg = dataclasses.replace(fam.program_config(doc), dtype=jnp.float32)
    toks = [random.Random(1).randrange(1, 256) for _ in range(90)]
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(llama.forward(p32, jnp.asarray([toks], jnp.int32), cfg)[0])
    ref = fam.reference.Reference(doc, pad_to=128)  # padded: the tail must be inert
    chosen = toks[1:] + [0]
    top, arg, got = ref.logits_of(params, toks, chosen)
    np.testing.assert_allclose(top, logits.max(-1), atol=2e-5)
    assert (arg == logits.argmax(-1)).all()
    np.testing.assert_allclose(got, logits[np.arange(90), chosen], atol=2e-5)


def test_weights_are_a_pure_function_of_the_seed_and_are_bf16():
    doc = tiny_doc()
    a, b, c = (fam.make_params(doc, s) for s in (5, 5, 6))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(a))
    assert all((x == y).all() for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not (a["lm_head"] == c["lm_head"]).all()
    assert fam.n_params(cells.load_config("mistral-7b-v0.3")) == 3_758_231_552
    assert fam.n_params(cells.load_config("internlm2-1.8b")) == 1_889_110_016


def test_family_refuses_what_llamaconfig_cannot_express():
    for bad in ({"tie_word_embeddings": True}, {"head_dim": 32}, {"sliding_window": 4096},
                {"torch_dtype": "float16"}):
        with pytest.raises(ValueError):
            fam.validate(tiny_doc(**bad))


def recs(n):
    return [{"i": i, "state": "SUCCEEDED", "prompt_len": 10 + i, "n_tokens": 20,
             "prompt": [1] * (10 + i), "tokens": [2] * 20} for i in range(n)]


def test_sample_has_the_longest_and_enough_tokens_and_follows_the_seed():
    rs = recs(30) + [{"i": 99, "state": "FAILED", "prompt_len": 999, "n_tokens": 5}]
    a = reference.pick_sample(rs, seed=1, min_tokens=100, max_requests=16)
    assert a[0]["i"] == 29 and len(a) == 5 and all(r["state"] == "SUCCEEDED" for r in a)
    assert [r["i"] for r in a] == [r["i"] for r in reference.pick_sample(rs, 1, 100, 16)]
    assert [r["i"] for r in a] != [r["i"] for r in reference.pick_sample(rs, 2, 100, 16)]
    assert len(reference.pick_sample(rs, 1, 10 ** 6, 7)) == 7
    assert reference.pick_sample([], 1, 100, 16) == []


def test_stream_faults_and_verdict():
    ok = {"i": 0, "state": "SUCCEEDED", "gaps": 0, "dups": 0, "n_tokens": 4, "want": 4,
          "stream_equals_result": True}
    assert reference.stream_faults([ok]) == []
    bad = [{**ok, "i": 1, "gaps": 1}, {**ok, "i": 2, "dups": 2}, {**ok, "i": 3, "n_tokens": 3},
           {**ok, "i": 4, "stream_equals_result": False}]
    assert [f["i"] for f in reference.stream_faults([ok] + bad)] == [1, 2, 3, 4]
    good, rows = reference.verdict({"a": 0.1, "b": 0}, {"a": 0.2, "b": 0})
    assert good and all(r["ok"] for r in rows)
    assert not reference.verdict({"a": 0.3, "b": 0}, {"a": 0.2, "b": 0})[0]
    assert not reference.verdict({"a": float("nan"), "b": 0}, {"a": 0.2, "b": 0})[0]


@pytest.mark.parametrize("config", ["mistral-7b-v0.3", "internlm2-1.8b"])
def test_control_in_int8_is_not_correct(config):
    """The configuration's published depth, heads of 128 and vocabulary at a
    hidden size a CPU test can hold: the reference stands in for a sound
    program (gap 0 by construction), its int8 self for a lower precision."""
    full = cells.load_config(config)
    doc = {**full, "hidden_size": 256, "intermediate_size": 512,
           "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 4096}
    params = fam.make_params(doc, seed=11)
    ref = fam.reference.Reference(doc, pad_to=160)
    rng = random.Random(3)
    sample = []
    for i in range(3):
        prompt = [rng.randrange(1, 4096) for _ in range(40)]
        # 'served' tokens: the reference's own greedy continuation, teacher-forced
        toks = [rng.randrange(1, 4096) for _ in range(100)]
        _, arg, _ = ref.logits_of(params, prompt + toks[:-1], [0] * 139)
        sample.append({"prompt": prompt, "tokens": toks[:1] + [int(t) for t in arg[40:]]})
    chk = full["check"]
    limits = {"gap_mean": chk["gap_mean_limit"], "gap_max": chk["gap_max_limit"]}
    control = reference.gaps_of(ref, params, sample, control=True)
    assert not reference.verdict(control, limits)[0], control
