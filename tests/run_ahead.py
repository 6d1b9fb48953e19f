"""What the tests of the dispatch ahead share (``tests/test_run_ahead.py``:
the rule; ``tests/test_run_ahead_parity.py``: parity with the engine that
waits; the parity classes of ``tests/test_engine.py``,
``tests/test_decode_fastpath.py`` and ``tests/test_experts_touched.py``): the
engine whose rule is patched to "never", the pair of runs, an engine at these
tests' sizes. Not collected.
"""

import numpy as np

from llm_d_kv_cache_manager_tpu.models import TINY_LLAMA
from llm_d_kv_cache_manager_tpu.server import (
    BlockManagerConfig,
    Engine,
    EngineConfig,
    SamplingParams,
    SchedulerConfig,
)

PS = 4


def never_admits_ahead(monkeypatch):
    """The engine whose admissions wait for their step (the dispatch ahead
    stays what it is): ``Engine._admit_ahead`` does nothing."""
    monkeypatch.setattr(Engine, "_admit_ahead", lambda self, active, k: None)


def never_ahead(monkeypatch):
    """The engine that waits: the one predicate answers no, and no
    admission goes ahead either."""
    monkeypatch.setattr(
        Engine, "_next_schedule_decided", lambda self, active, k: False
    )
    never_admits_ahead(monkeypatch)


def both(make, drive, monkeypatch, chained=True):
    """``drive(make())`` under the rule and under "never": the two
    results, asserted equal. ``chained``: the run under the rule must have
    chained a dispatch (else the case holds nothing), the other none."""
    outs = []
    for never in (False, True):
        if never:
            never_ahead(monkeypatch)
        eng = make()
        eng.obs_step_timing = True
        outs.append(drive(eng))
        assert eng._inflight is None and not eng.has_work
        n = eng.step_stats["decode_chained_dispatches"]
        assert (n > 0) == (chained and not never), (never, n)
    monkeypatch.undo()
    assert outs[0] == outs[1]
    return outs[0]


def make_engine(total_pages=64, lanes=2, model=TINY_LLAMA, params=None,
            max_model_len=64, on_events=None, **kw):
    kw.setdefault("scheduler", SchedulerConfig(max_prefill_batch=4))
    kw.setdefault("prefill_bucket", 8)
    return Engine(
        EngineConfig(
            model=model,
            block_manager=BlockManagerConfig(
                total_pages=total_pages, page_size=PS
            ),
            max_model_len=max_model_len,
            decode_batch_size=lanes,
            interpret=True,
            **kw,
        ),
        params=params, on_events=on_events,
    )


def prompt(seed, n, vocab=TINY_LLAMA.vocab_size):
    return list(map(int, np.random.default_rng(seed).integers(1, vocab, n)))


def fill(eng, n, new=20, seed=0, plen=9):
    return [
        eng.add_request(
            prompt(seed + i, plen + i), SamplingParams(max_new_tokens=new),
            request_id=f"r{seed + i}",
        )
        for i in range(n)
    ]
