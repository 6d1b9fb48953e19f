"""The forms of the full-context decode call (``paged_attention`` without
``window=``, a walk of a lane's own pages since PR 57) beside
``tests/test_paged_attention_walk.py``'s float32 cases: GQA groups of 6, 7
and 8 at 128-wide heads, bfloat16 pools, a table wide enough for steps of 512
tokens, int8 pools through the walked body, and a ``tp`` shard's heads on the
CPU mesh; and a group of single copies as a loop and without one."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import quant
from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from window_walks import (
    FULL_TABLES,
    FULL_WALKS,
    check_full_walk,
    full_setup,
    with_fresh_written,
)


class TestFullWalkForms:
    @pytest.mark.parametrize("group", [6, 7, 8])
    @pytest.mark.parametrize(
        "case", ["history-ends-mid-step", "lanes-of-length-0-beside-live-ones"])
    def test_the_groups_of_the_served_models(self, case, group):
        check_full_walk(case, True, "one-run", heads=(2, group), d=128)

    @pytest.mark.parametrize("kind", FULL_TABLES)
    @pytest.mark.parametrize(
        "case", ["history-ends-mid-page", "history-ends-on-a-steps-last-slot",
                 "lanes-of-length-0-beside-live-ones"])
    def test_bfloat16_pools(self, case, kind):
        check_full_walk(case, True, kind, layer=1, dtype=jnp.bfloat16)

    @pytest.mark.parametrize("kind", FULL_TABLES)
    @pytest.mark.parametrize(
        "case", ["history-ends-mid-step", "history-ends-on-a-steps-last-slot"])
    def test_a_wide_tables_step_is_two_blocks(self, case, kind):
        """From ``WIDE_TABLE_TOKENS`` on a step is 512 tokens
        (``walk_step_pages``): the cases are what their names say there too."""
        check_full_walk(case, True, kind, layer=0, wide=True)


class TestTheGroupRolledAndStraight:
    """A group of pages that is no run is a loop of single copies in the
    full-context call, on the chip as under the interpreter (PR 57: a warm
    start loads what the kernel holds), and ``RUN_PAGES`` copies without a
    loop in the sliding layers' call on the chip (the form PR 45 swept),
    which the interpreter is given as the loop too (220 lines of HLO a
    copy). The two forms give one answer to the bit; the straight one is
    interpreted here and nowhere else."""

    @staticmethod
    def _straight(monkeypatch):
        """``for_step_pages`` without the loop, whatever the call asks for;
        returns what the calls asked for."""
        module = sys.modules[paged_attention.__module__]
        asked = []

        def straight(*args, rolled_=module.for_step_pages, **kw):
            asked.append(kw.pop("rolled"))
            return rolled_(*args, rolled=False, **kw)

        monkeypatch.setattr(module, "for_step_pages", straight)
        return asked

    @pytest.mark.parametrize("kind", FULL_TABLES)
    def test_the_full_calls_group(self, kind, monkeypatch):
        case = "history-ends-mid-step"
        want = check_full_walk(case, True, kind)
        ps, hists = FULL_WALKS[case]
        q, k, v, tables, lens, fk, fv = full_setup(31, ps, hists, kind)
        asked = self._straight(monkeypatch)
        got = paged_attention.__wrapped__(  # no cached trace of the loop's form
            q, k, v, tables, jnp.array(lens, jnp.int32), fk, fv,
            interpret=True, layer=2)
        assert asked and all(asked)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_the_window_calls_group_as_the_chip_runs_it(self, monkeypatch):
        from window_walks import WINDOW_WALKS, window_setup

        ps, window, pages, lens = WINDOW_WALKS["history-ends-mid-block"]
        q, k, v, tables, starts, abs_lens, fk, fv = window_setup(21, ps, pages, lens)
        call = dict(interpret=True, layer=1, window=window, table_start=starts)
        want = paged_attention(q, k, v, tables, abs_lens, fk, fv, **call)
        asked = self._straight(monkeypatch)
        got = paged_attention.__wrapped__(q, k, v, tables, abs_lens, fk, fv, **call)
        assert asked and all(asked)  # the interpreter's form is the loop
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_which_calls_group_is_a_loop_on_the_chip(monkeypatch):
    """Compiled (``interpret=False``), the full-context call asks for the
    loop and the sliding layers' call for the straight group: from the
    call's ``window``, no argument of a caller's."""
    module = sys.modules[paged_attention.__module__]
    asked = {}

    def stop(kernel, *a, name, **kw):
        asked[name] = kernel.keywords["rolled"]
        raise StopIteration

    monkeypatch.setattr(module, "require_tpu_unless_interpret", lambda *a: None)
    monkeypatch.setattr(module.pl, "pallas_call", stop)
    ps, hists = FULL_WALKS["history-ends-mid-page"]
    q, k, v, tables, lens, fk, fv = full_setup(35, ps, hists, "one-run")
    for window in (0, 64):
        with pytest.raises(StopIteration):
            paged_attention.__wrapped__(
                q, k, v, tables, jnp.array(lens, jnp.int32), fk, fv,
                window=window)
    assert asked == {"paged_attention": True, "paged_attention_window": False}


def _int8_pools(rng, layers, total, ps, n_kv, d):
    codes = rng.integers(-127, 128, (2, layers, total, ps, n_kv, d)).astype(np.int8)
    scales = rng.uniform(0.002, 0.02, (2, layers, total, n_kv)).astype(np.float32)
    wide = [quant.dequantize_kv_pool(c, s, np.float32) for c, s in zip(codes, scales)]
    return jnp.asarray(codes), jnp.asarray(scales), wide


class TestInt8Walk:
    """int8 pools through the walked body: codes copied as they lie, a
    lane's scales gathered in table order and taken by the scores and the
    probabilities. Against the oracle on the dequantised pools: both sides
    see the same values, so the tolerance is a float's rounding."""

    @pytest.mark.parametrize("kind", FULL_TABLES)
    @pytest.mark.parametrize("fresh", [False, True], ids=["resident", "fresh"])
    @pytest.mark.parametrize(
        "case", ["history-ends-mid-step", "history-ends-on-a-steps-last-slot",
                 "lanes-of-length-0-beside-live-ones", "pages-of-4"])
    def test_matches_reference_on_the_dequantised_pools(self, case, fresh, kind):
        ps, hists = FULL_WALKS[case]
        q, k, _, tables, lens, fk, fv = full_setup(41, ps, hists, kind)
        layers, total, _, n_kv, d = k.shape
        codes, scales, wide = _int8_pools(
            np.random.default_rng(42), layers, total, ps, n_kv, d)
        layer = 1
        k_ref, v_ref = jnp.asarray(wide[0][layer]), jnp.asarray(wide[1][layer])
        sl = jnp.array(lens, jnp.int32) - (0 if fresh else 1)
        sl = jnp.maximum(sl, 0)
        if fresh:
            k_ref, v_ref = with_fresh_written(
                jnp.asarray(wide[0]), jnp.asarray(wide[1]), tables, lens, fk, fv,
                layer, ps)
        got = paged_attention(
            q, codes[0], codes[1], tables, sl, *((fk, fv) if fresh else ()),
            k_scale=scales[0], v_scale=scales[1], interpret=True,
            layer=jnp.int32(layer))
        want = paged_attention_reference(q, k_ref, v_ref, tables, sl)
        np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


class TestTpShard:
    """The walked body inside ``shard_map`` on a shard's heads
    (``llama._paged_attention_tp``), on the CPU mesh: what one device
    computes over all heads."""

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16-form", "int8"])
    def test_a_shards_heads_are_the_whole_calls(self, int8):
        from llm_d_kv_cache_manager_tpu.models.llama import _paged_attention_tp
        from llm_d_kv_cache_manager_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
        ps, hists = FULL_WALKS["history-ends-mid-step"]
        q, k, v, tables, lens, fk, fv = full_setup(
            51, ps, hists, "one-run", heads=(4, 2))
        sl = jnp.array(lens, jnp.int32)
        seen = {}
        if int8:
            layers, total, _, n_kv, d = k.shape
            (k, v), (ks, vs), _ = _int8_pools(
                np.random.default_rng(52), layers, total, ps, n_kv, d)
            seen = dict(k_scale=ks, v_scale=vs)
        want = _paged_attention_tp(
            q, k, v, tables, sl, fk, fv, interpret=True, mesh=None, layer=1,
            **seen)
        got = jax.jit(lambda *a, **kw: _paged_attention_tp(
            *a, interpret=True, mesh=mesh, layer=1, **kw))(
                q, k, v, tables, sl, fk, fv, **seen)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
