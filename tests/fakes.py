"""The tests' one stand-in for a serving backend, and its oracle."""
import asyncio
import time

from cordum_tpu.serving.backend import StepBackend

MOD = 251  # the fake's sample modulus


class FakeBackend(StepBackend):
    """A backend of host integers behind the engine's contract
    (``StepBackend``): a page holds the tokens written to it, and every
    sample is ``(sum * 3 + count) % 251`` over the row's WHOLE written
    prefix, read back through the page table.  So a wrong table, a missed
    copy-on-write, a skipped prefill or a bad import changes the emitted
    tokens.  A draft row gets one such prediction per fed position.
    ``copy_page`` / ``export_kv`` / ``import_kv`` move real slots.

    It keeps what it was handed (``seen``, rows per step in
    ``decode_batches``), sleeps ``step_delay`` a step and ``slow_at[n]``
    more in step ``n``, and raises in the steps of ``fail_at``."""

    def __init__(self, num_pages=16, page_size=4, max_context=64,
                 step_delay=0.0, max_seqs=16, max_batch_tokens=32,
                 slow_at=None, fail_at=()):
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_context = max_context
        self.max_seqs = max_seqs
        self.max_batch_tokens = max_batch_tokens
        self.step_delay = step_delay
        self.slow_at = dict(slow_at or {})
        self.fail_at = set(fail_at)
        self.steps = 0
        self.decode_batches: list[int] = []  # rows per mixed step
        self.seen: list[list[tuple]] = []  # (tokens, start, phase, draft) a row
        self.prefills = 0  # completed prompts
        self.prefill_chunks = 0
        self.fed_prefill: dict[str, int] = {}  # key -> prompt tokens fed
        self.arena: dict[int, list[int]] = {}
        self.copies = 0

    def _row(self, page):
        return self.arena.setdefault(page, [0] * self.page_size)

    def _read(self, pages, n):
        ps = self.page_size
        return [self._row(pages[i // ps])[i % ps] for i in range(n)]

    @staticmethod
    def sample(seq):
        return (sum(seq) * 3 + len(seq)) % MOD

    def step(self, entries):
        t0 = time.time_ns()
        n_step = self.steps
        self.steps += 1
        if self.on_dispatched is not None:
            self.on_dispatched()  # no feed of its own: fed on entry
        self.device(n_step)
        if n_step in self.fail_at:
            raise RuntimeError("poisoned")
        # the static-shape contract the real backend enforces
        assert len(entries) <= self.max_seqs, "max_seqs exceeded"
        assert sum(len(e.tokens) for e in entries) <= self.max_batch_tokens, \
            "flat token budget exceeded"
        self.last_step_compiled = n_step == 0  # one program, one compile
        self.decode_batches.append(len(entries))
        self.seen.append(
            [(list(e.tokens), e.start, e.phase, e.draft) for e in entries])
        ps = self.page_size
        out = []
        for e in entries:
            for i, t in enumerate(e.tokens):
                pos = e.start + i
                self._row(e.pages[pos // ps])[pos % ps] = t
            written = e.start + len(e.tokens)
            if e.phase == "prefill":
                self.prefill_chunks += 1
                self.fed_prefill[e.key] = (
                    self.fed_prefill.get(e.key, 0) + len(e.tokens))
                self.prefills += bool(e.sample)
            if e.draft > 0:
                seq = self._read(e.pages, written)
                out.append([self.sample(seq[:e.start + i + 1])
                            for i in range(len(e.tokens))])
            elif e.sample:
                out.append(self.sample(self._read(e.pages, written)))
            else:
                out.append(None)
        if self.on_step is not None:
            self.on_step(entries)
        self.stamp_whole_call(t0)
        return out

    def device(self, n_step):
        """Where the step's device time passes."""
        stall = self.step_delay + self.slow_at.get(n_step, 0.0)
        if stall:
            time.sleep(stall)

    def copy_page(self, src, dst):
        self.arena[dst] = list(self._row(src))
        self.copies += 1

    def export_kv(self, pages, start_tok, end_tok):
        if end_tok <= start_tok:
            return []
        ps = self.page_size
        first, last = start_tok // ps, -(-end_tok // ps)
        recs = []
        for o in range(first, min(last, len(pages))):
            used = min(ps, end_tok - o * ps)
            recs.append({"i": o, "used": used,
                         "k": list(self._row(pages[o])[:used]), "v": [],
                         "shape": [used]})
        return recs

    def import_kv(self, pages, records):
        for rec in records:
            row = [0] * self.page_size
            row[:len(rec["k"])] = rec["k"]
            self.arena[pages[rec["i"]]] = row


def fake_ref(prompt, n_new):
    """Sequential oracle of ``FakeBackend``: each sample is a function of
    the entire written prefix, so any aliasing corruption diverges."""
    seq = list(prompt)
    out = [FakeBackend.sample(seq)]
    for _ in range(n_new - 1):
        seq.append(out[-1])
        out.append(FakeBackend.sample(seq))
    return out


async def run_blocking(fn, *args):
    return await asyncio.get_running_loop().run_in_executor(None, fn, *args)
