"""The generator is a pure function of (traffic file, seed): the same seed
gives the same requests, another seed the same lengths and gaps in another
order."""
import pytest

from benchmarks.harness import cells, traffic

KW = dict(seconds=40.0, vocab=32768, context=2048, max_new_cap=256)
MIXES = ["chat-open", "toolcalls-open", "chat-closed"]


@pytest.mark.parametrize("mix", MIXES)
def test_pure_function_of_the_seed(mix):
    tr = cells.load_traffic(mix)
    a = traffic.generate(tr, seed=2 ** 31 + 17, **KW)
    b = traffic.generate(tr, seed=2 ** 31 + 17, **KW)
    c = traffic.generate(tr, seed=18, **KW)
    assert a == b and a != c
    assert all(1 <= t < KW["vocab"] for r in a for t in r["tokens"])
    assert all(len(r["tokens"]) + r["max_new_tokens"] <= KW["context"] for r in a)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_schedule_with_other_contents(mix):
    tr = cells.load_traffic(mix)
    a, b = (traffic.generate(tr, seed=s, **KW) for s in (3, 4))

    def schedule(reqs):
        return [(len(r["tokens"]), r["max_new_tokens"], r.get("due_s")) for r in reqs]

    assert schedule(a) == schedule(b)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in b]
    other = traffic.generate({**tr, "schedule_seed": tr["schedule_seed"] + 1}, seed=3, **KW)
    assert schedule(other) != schedule(a)                       # another order: another file
    assert sorted(x[0] for x in schedule(other)) == sorted(
        x[0] for x in schedule(a))                              # ... of the same lengths
    if tr["loop"] == "open":
        assert len(a) == round(tr["rate_rps"] * KW["seconds"])
        assert a[0]["due_s"] == 0.0 and max(r["due_s"] for r in a) < KW["seconds"]
        assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    else:  # closed: whole blocks, each block the same multiset
        blk = tr["block"]
        assert len(a) % blk == 0 and len(a) >= tr["pool_rps"] * KW["seconds"]
        block = sorted(traffic.lengths(tr["prompt_tokens"], blk))
        for k in range(0, len(a), blk):
            assert sorted(len(r["tokens"]) for r in a[k:k + blk]) == block


def test_length_distribution_is_the_stated_one():
    tr = cells.load_traffic("chat-open")
    lens = sorted(len(r["tokens"]) for r in traffic.generate(tr, seed=1, **KW))
    assert lens[0] >= 32 and lens[-1] == 1024
    assert abs(lens[len(lens) // 2] - 256) <= 16  # the median


def test_sessions_turns_and_shared_prefix_need_no_new_code():
    tr = {"loop": "open", "rate_rps": 2.0, "arrivals": {"process": "poisson"},
          "prompt_tokens": {"dist": "uniform", "min": 64, "max": 256},
          "new_tokens": {"dist": "uniform", "min": 32, "max": 128},
          "sessions": {"turns": [4, 8], "shared_prefix_tokens": 512, "think_s": [1.0, 3.0]}}
    reqs = traffic.generate(tr, seed=9, seconds=10.0, vocab=1000, context=2048, max_new_cap=256)
    firsts = [r for r in reqs if r["turn"] == 0]
    assert len(firsts) == 20 and all("due_s" in r for r in firsts)
    assert len({tuple(r["tokens"][:512]) for r in firsts}) == 1  # one shared system prompt
    for r in reqs:
        if r["turn"]:
            prev = reqs[r["after"]]
            assert prev["session"] == r["session"] and prev["turn"] == r["turn"] - 1
            assert 1.0 <= r["think_s"] <= 3.0 and "due_s" not in r
    # a session never outgrows the context
    for s in {r["session"] for r in reqs}:
        total = sum(len(r["tokens"]) + r["max_new_tokens"] for r in reqs if r["session"] == s)
        assert total <= 2048


def test_bursts():
    tr = {"loop": "open", "arrivals": {"process": "bursts", "size": 20, "period_s": 2.0},
          "prompt_tokens": {"dist": "fixed", "value": 10, "min": 1, "max": 10},
          "new_tokens": {"dist": "fixed", "value": 4, "min": 1, "max": 4}}
    reqs = traffic.generate(tr, seed=1, seconds=10.0, vocab=100, context=64, max_new_cap=8)
    assert len(reqs) == 100 and sorted({r["due_s"] for r in reqs}) == [0.0, 2.0, 4.0, 6.0, 8.0]
