"""A model with sliding layers: every way a row lies in the two pools in
``llama.prefill`` and ``llama.decode_step`` (a prompt of half a window to
four, whole or chunked, a chunk's own keys, a window table that starts
mid-context, a page reused after it was given back, a right-padded batch of
unequal lengths), each held to ``chipbench/references/swa_moe.forward`` at
``TINY_SWA_MOE`` in float32, by both prefill paths. The window's edge is in
``tests/test_swa.py``.
"""

import pytest

import served_path
from chipbench import reference as chip_reference
from llm_d_kv_cache_manager_tpu.models import TINY_SWA_MOE
from served_path import prompt_of, rel_err

CFG = TINY_SWA_MOE
PS = 4
W = CFG.sliding_window
TOL = chip_reference.TOL_F32
REF = chip_reference.load("swa_moe")


@pytest.fixture(scope="module")
def params():
    return served_path.params_of(CFG, 43)


def reference_logits(params, tokens):
    return served_path.reference_logits(REF, params, CFG, tokens)


def served(params, rows, steps, attn_impl):
    got, fed, _ = served_path.served(
        params, CFG, rows, steps, attn_impl, page_size=PS,
        second=served_path.WindowPages(REF.WindowTable, PS))
    return got, fed


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [
    pytest.param([(W // 2, 0)], id="half-a-window-whole"),
    pytest.param([(W, 0)], id="one-window-whole"),
    pytest.param([(5 * W // 2, 0)], id="two-and-a-half-windows-whole"),
    pytest.param([(4 * W, 0)], id="four-windows-whole"),
    pytest.param([(5 * W // 2, W)], id="two-and-a-half-windows-chunked"),
    pytest.param([(4 * W, 3 * W - PS)], id="four-windows-chunked"),
    pytest.param([(4 * W, 2 * W), (W // 2, 0), (5 * W // 2, W), (W + 1, 0)],
                 id="batch-of-unequal-lengths"),
])
def test_prefill_then_decode_through_both_pools(params, rows, attn_impl):
    rows = [(prompt_of(40 + i, n), r) for i, (n, r) in enumerate(rows)]
    # 6 steps: every row crosses a page and gives a window page back
    got, fed = served(params, rows, 6, attn_impl)
    for (prompt, _), logits, tokens in zip(rows, got, fed):
        want = reference_logits(params, prompt + tokens)[len(prompt) - 1:]
        assert rel_err(logits, want) < TOL
