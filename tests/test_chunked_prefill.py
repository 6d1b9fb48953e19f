"""Chunked prefill + mixed-step scheduling tests.

Load-bearing invariants:
- with ``chunked_prefill_tokens`` unset the scheduler/engine are
  bit-identical to the legacy either-or engine (the existing
  ``test_engine.py`` determinism tests pin the engine side; the scheduler
  unit tests here pin the schedule shapes);
- with chunking ON, greedy outputs are bit-identical to the unchunked
  engine — including prefix-cache-hit prompts and preemption mid-prefill;
- a waiting/ingesting long prompt never starves running decode lanes: every
  mixed step carries the lanes, and they commit tokens during ingest.
"""

import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.server import (
    BlockManager,
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    Scheduler,
    SchedulerConfig,
    Sequence,
)

PS = 4


def _engine(total_pages=64, decode_batch=4, chunked=None, **kw):
    cfg = EngineConfig(
        model=TINY_LLAMA,
        block_manager=BlockManagerConfig(total_pages=total_pages, page_size=PS),
        scheduler=SchedulerConfig(
            max_prefill_batch=4, chunked_prefill_tokens=chunked
        ),
        max_model_len=64,
        decode_batch_size=decode_batch,
        prefill_bucket=8,
        interpret=True,
        **kw,
    )
    return Engine(cfg)


def _prompt(seed, n):
    return list(np.random.default_rng(seed).integers(0, TINY_LLAMA.vocab_size, n))


class TestChunkedSchedulerUnit:
    """Scheduler-level behavior, no device dispatch."""

    def _sched(self, chunked=8, total_pages=64, **kw):
        bm = BlockManager(BlockManagerConfig(total_pages=total_pages, page_size=PS))
        return Scheduler(
            bm,
            SchedulerConfig(
                max_prefill_batch=4,
                chunked_prefill_tokens=chunked,
                chunk_align=8,
                **kw,
            ),
        )

    def test_long_prompt_never_starves_decode_lanes(self):
        # A running decode lane + a waiting 40-token prompt with budget 8:
        # EVERY step until the prompt finishes ingesting must carry the
        # decode lane (the stall-free property under test).
        sched = self._sched(chunked=8)
        lane = Sequence(prompt_tokens=_prompt(0, 6))
        sched.block_manager.allocate(lane)
        lane.num_prefilled = 6
        sched.on_prefill_done([lane])

        long = Sequence(prompt_tokens=_prompt(1, 40))
        sched.add(long)
        steps = 0
        while long.prompt_remaining > 0:
            out = sched.schedule()
            assert out.decode == [lane], "decode lane starved during ingest"
            assert out.prefill == [long] and len(out.chunks) == 1
            # simulate the engine committing the chunk
            long.num_prefilled += out.chunks[0]
            steps += 1
            assert steps < 50
        assert steps == 5  # 40 tokens / 8-token budget
        sched.on_prefill_done([long])
        assert long in sched.running and not sched.prefilling

    def test_nonfinal_chunks_are_aligned_final_is_remainder(self):
        sched = self._sched(chunked=12)  # not an align multiple
        seq = Sequence(prompt_tokens=_prompt(2, 21))
        sched.add(seq)
        sizes = []
        while seq.prompt_remaining > 0:
            out = sched.schedule()
            sizes.append(out.chunks[0])
            seq.num_prefilled += out.chunks[0]
        # budget 12 floors to align=8 for non-final chunks; remainder last
        assert sizes == [8, 8, 5]
        for s in sizes[:-1]:
            assert s % 8 == 0

    def test_budget_smaller_than_align_still_progresses(self):
        sched = self._sched(chunked=3)
        seq = Sequence(prompt_tokens=_prompt(3, 17))
        sched.add(seq)
        out = sched.schedule()
        assert out.chunks == [8]  # clamped up to one alignment unit

    def test_max_prefill_tokens_below_align_cannot_livelock(self):
        # Regression: the align clamp must win over max_prefill_tokens —
        # a budget pulled below one alignment unit would otherwise produce
        # zero-token chunks forever (allocate/rollback every step while
        # has_work stays True).
        sched = self._sched(chunked=16, max_prefill_tokens=4)
        seq = Sequence(prompt_tokens=_prompt(9, 30))
        sched.add(seq)
        steps = 0
        while seq.prompt_remaining > 0:
            out = sched.schedule()
            assert out.chunks and out.chunks[0] > 0
            seq.num_prefilled += out.chunks[0]
            steps += 1
            assert steps < 20

    def test_admission_rolls_back_when_budget_exhausted(self):
        # First prompt eats the whole budget; the second must NOT hold
        # pages while doing zero work this step.
        sched = self._sched(chunked=8)
        a = Sequence(prompt_tokens=_prompt(4, 24))
        b = Sequence(prompt_tokens=_prompt(5, 24))
        sched.add(a)
        sched.add(b)
        out = sched.schedule()
        assert out.prefill == [a] and out.chunks == [8]
        assert not b.block_table and b in sched.waiting
        free_with_b_waiting = sched.block_manager.num_free
        # a's pages are held, b's are not
        assert free_with_b_waiting == 64 - 1 - 6  # page 0 reserved, a = 6 pages

    def test_resume_prioritized_over_new_admission(self):
        sched = self._sched(chunked=16)
        a = Sequence(prompt_tokens=_prompt(6, 24))
        sched.add(a)
        out = sched.schedule()
        assert out.prefill == [a]
        a.num_prefilled += out.chunks[0]
        b = Sequence(prompt_tokens=_prompt(7, 24))
        sched.add(b)
        out = sched.schedule()
        # a resumes first; leftover budget admits b
        assert out.prefill[0] is a and out.chunks[0] == 8
        assert out.prefill[1] is b and out.chunks[1] == 8

    def test_legacy_mode_unchanged_when_knob_unset(self):
        sched = self._sched(chunked=None)
        a = Sequence(prompt_tokens=_prompt(8, 12))
        sched.add(a)
        out = sched.schedule()
        assert out.prefill == [a] and out.chunks is None and out.decode == []
        a.num_prefilled = 12
        sched.on_prefill_done([a])
        out = sched.schedule()
        assert out.prefill == [] and out.decode == [a]


class TestChunkedPrefillParity:
    """Greedy outputs must be bit-identical chunked vs unchunked. Also the
    tier-1 CPU smoke of the mixed-step path (fast, runs every commit)."""

    def test_single_long_prompt_matches(self):
        outs = []
        for chunked in (None, 8):
            eng = _engine(chunked=chunked)
            s = eng.add_request(_prompt(10, 40), SamplingParams(max_new_tokens=6))
            eng.run_until_complete()
            assert s.error is None
            outs.append(s.output_tokens)
        assert outs[0] == outs[1]
        assert len(outs[0]) == 6

    def test_mixed_arrivals_match_and_decode_advances_during_ingest(self):
        def drive(chunked):
            eng = _engine(chunked=chunked)
            a = eng.add_request(_prompt(11, 6), SamplingParams(max_new_tokens=14))
            b = eng.add_request(_prompt(12, 9), SamplingParams(max_new_tokens=14))
            for _ in range(3):
                eng.step()
            c = eng.add_request(_prompt(13, 41), SamplingParams(max_new_tokens=5))
            during_ingest = 0
            while c.num_generated == 0 and eng.has_work:
                g0 = a.num_generated + b.num_generated
                eng.step()
                if c.num_generated == 0:
                    during_ingest += a.num_generated + b.num_generated - g0
            eng.run_until_complete()
            return [a.generated_tokens, b.generated_tokens, c.generated_tokens], during_ingest

        base, stalled = drive(None)
        chk, streamed = drive(8)
        assert base == chk
        # The mechanism: with either-or scheduling the lanes commit nothing
        # while the 41-token prompt prefills; with a 8-token budget they
        # keep streaming through the ~5 chunk steps.
        assert stalled == 0
        assert streamed >= 4

    def test_prefix_cache_hit_prompts_match(self):
        shared = _prompt(42, 16)  # 4 full pages
        outs = []
        for chunked in (None, 8):
            eng = _engine(chunked=chunked)
            a = eng.add_request(
                shared + _prompt(14, 20), SamplingParams(max_new_tokens=4)
            )
            eng.run_until_complete()
            b = eng.add_request(
                shared + _prompt(15, 24), SamplingParams(max_new_tokens=4)
            )
            eng.run_until_complete()
            assert b.num_cached_prompt == 16
            outs.append((a.output_tokens, b.output_tokens))
        assert outs[0] == outs[1]

    def test_chunked_pages_feed_prefix_cache_mid_prefill(self):
        # Pages registered by non-final chunks are real prefix-cache
        # entries: a follow-up sharing the long prompt's prefix cache-hits
        # pages written chunk by chunk.
        p = _prompt(16, 40)
        eng = _engine(chunked=8)
        a = eng.add_request(p, SamplingParams(max_new_tokens=3))
        eng.run_until_complete()
        b = eng.add_request(list(p), SamplingParams(max_new_tokens=3))
        eng.run_until_complete()
        assert b.num_cached_prompt >= 36  # all but the last partial page
        assert a.output_tokens == b.output_tokens

    def test_preemption_mid_prefill_matches(self):
        # Pool sized so the decode lane's growth must preempt the long
        # prompt mid-prefill (chunked mode holds its pages across steps);
        # everything still completes with identical tokens.
        def drive(chunked):
            eng = _engine(chunked=chunked, total_pages=16, decode_batch=2)
            a = eng.add_request(_prompt(17, 8), SamplingParams(max_new_tokens=20))
            eng.step()  # a prefills and starts decoding
            b = eng.add_request(_prompt(18, 33), SamplingParams(max_new_tokens=4))
            eng.run_until_complete()
            assert a.error is None and b.error is None
            return [a.generated_tokens, b.generated_tokens]

        base = drive(None)
        chk = drive(8)
        assert base == chk
        assert len(base[0]) == 20 and len(base[1]) == 4

    def test_env_knob_wires_chunked_prefill(self, monkeypatch):
        from llm_d_kv_cache_manager_tpu.server.serve import PodServerConfig

        monkeypatch.setenv("CHUNKED_PREFILL_TOKENS", "512")
        cfg = PodServerConfig.from_env()
        assert cfg.engine.scheduler.chunked_prefill_tokens == 512
        monkeypatch.setenv("CHUNKED_PREFILL_TOKENS", "0")
        assert PodServerConfig.from_env().engine.scheduler.chunked_prefill_tokens is None

    def test_rejects_bad_budget(self):
        with pytest.raises(ValueError, match="chunked_prefill_tokens"):
            _engine(chunked=0)


class TestChunkedInterference:
    """Heavier chunked-prefill coverage: the interference microbenchmark
    as a test, plus parity sweeps against the other decode paths
    (marked ``slow`` by the table in ``tests/conftest.py``: no run executes
    them)."""

    @pytest.mark.parametrize(
        "kw",
        [
            dict(decode_steps_per_iter=3),
            # three lanes for the three requests: with lanes full the
            # engine runs one dispatch ahead, beside the chunks too
            dict(decode_steps_per_iter=3, decode_batch=3),
            dict(spec_decode="prompt_lookup", spec_k=3, spec_ngram=2),
            dict(spec_decode="prompt_lookup", spec_k=3, spec_ngram=2,
                 spec_rounds=3),
        ],
    )
    def test_parity_with_other_decode_paths(self, kw):
        # Chunked ingest composes with fused/run-ahead/speculative decode:
        # token streams stay identical to the unchunked engine running the
        # same decode config.
        rep = _prompt(20, 3) * 6  # repetition-heavy lane (exercises spec)
        prompts = [rep, _prompt(21, 9), _prompt(22, 38)]
        streams = []
        for chunked in (None, 8):
            eng = _engine(chunked=chunked, **kw)
            seqs = []
            for p in prompts:
                seqs.append(eng.add_request(p, SamplingParams(max_new_tokens=8)))
                eng.step()
            eng.run_until_complete()
            assert all(s.error is None for s in seqs), kw
            streams.append([s.generated_tokens for s in seqs])
        assert streams[0] == streams[1], kw

    def test_interference_microbench_mechanism(self):
        """The microbenchmark's mechanism, asserted deterministically
        (token counts, not wall time): decode lanes keep committing while
        a long prompt ingests chunked, and stall completely unchunked."""

        def drive(chunked):
            eng = _engine(chunked=chunked, total_pages=96)
            lanes = [
                eng.add_request(_prompt(30 + i, 6), SamplingParams(max_new_tokens=40))
                for i in range(2)
            ]
            while any(s.num_generated == 0 for s in lanes):
                eng.step()
            long = eng.add_request(_prompt(33, 48), SamplingParams(max_new_tokens=4))
            during = 0
            while long.num_generated == 0 and eng.has_work:
                g0 = sum(s.num_generated for s in lanes)
                eng.step()
                if long.num_generated == 0:
                    during += sum(s.num_generated for s in lanes) - g0
            eng.run_until_complete()
            assert long.error is None
            return during, [s.generated_tokens for s in lanes]

        stalled, base = drive(None)
        streamed, chk = drive(8)
        assert stalled == 0  # either-or: whole-prompt prefill stalls lanes
        assert streamed >= 4  # mixed steps: lanes stream through ingest
        assert base == chk
