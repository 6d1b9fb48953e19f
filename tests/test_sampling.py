"""Sampling ops: filtered distributions and speculative verification.

`spec_sample` implements deterministic-draft speculative sampling (accept
draft with prob P(draft); residual sample on rejection) — the invariants
below are what make the emitted stream an exact sample of the target
distribution, so they are pinned as pure-function tests.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.ops.sampling import sample_tokens, spec_sample

V = 16


def _logits(rng, b, s):
    return jnp.asarray(rng.standard_normal((b, s, V)) * 3.0, jnp.float32)


class TestSpecSample:
    def test_greedy_lanes_match_argmax_semantics(self):
        rng = np.random.default_rng(0)
        logits = _logits(rng, 2, 4)
        argmax = np.asarray(jnp.argmax(logits, -1))
        drafts = jnp.asarray(argmax.copy())
        drafts = drafts.at[0, 2].set((argmax[0, 2] + 1) % V)  # one mismatch
        accept, replacement, free = spec_sample(
            logits, drafts,
            jnp.zeros((2,)), jnp.zeros((2,), jnp.int32), jnp.ones((2,)),
            jax.random.PRNGKey(0),
        )
        accept = np.asarray(accept)
        assert accept[1].all() and accept[0, [0, 1, 3]].all()
        assert not accept[0, 2]
        np.testing.assert_array_equal(np.asarray(replacement), argmax)
        np.testing.assert_array_equal(np.asarray(free), argmax)

    def test_replacement_never_equals_draft_for_sampled_lanes(self):
        rng = np.random.default_rng(1)
        logits = _logits(rng, 3, 5)
        drafts = jnp.asarray(rng.integers(0, V, (3, 5)), jnp.int32)
        for seed in range(5):
            _, replacement, _ = spec_sample(
                logits, drafts,
                jnp.full((3,), 1.0), jnp.zeros((3,), jnp.int32), jnp.ones((3,)),
                jax.random.PRNGKey(seed),
            )
            assert not np.any(np.asarray(replacement) == np.asarray(drafts))

    def test_topk1_collapses_to_argmax(self):
        # A point-mass distribution: accept iff draft == argmax; free is
        # argmax; so temperature>0 behaves exactly like greedy.
        rng = np.random.default_rng(2)
        logits = _logits(rng, 2, 4)
        argmax = np.asarray(jnp.argmax(logits, -1))
        drafts = jnp.asarray(argmax)
        accept, _, free = spec_sample(
            logits, drafts,
            jnp.full((2,), 0.8), jnp.ones((2,), jnp.int32), jnp.ones((2,)),
            jax.random.PRNGKey(3),
        )
        assert np.asarray(accept).all()
        np.testing.assert_array_equal(np.asarray(free), argmax)

    def test_acceptance_rate_tracks_draft_probability(self):
        # Statistical: with temperature 1 and a known distribution, the
        # measured acceptance over many keys approaches P(draft).
        logits = jnp.log(
            jnp.asarray([[[0.7, 0.2, 0.1] + [1e-9] * (V - 3)]], jnp.float32)
        )
        drafts = jnp.zeros((1, 1), jnp.int32)  # P(draft) = 0.7
        hits = 0
        n = 400
        for seed in range(n):
            accept, _, _ = spec_sample(
                logits, drafts,
                jnp.ones((1,)), jnp.zeros((1,), jnp.int32), jnp.ones((1,)),
                jax.random.PRNGKey(seed),
            )
            hits += int(np.asarray(accept)[0, 0])
        assert 0.6 < hits / n < 0.8  # ~±4 sigma band around 0.7

    def test_free_samples_stay_in_topk_support(self):
        rng = np.random.default_rng(4)
        logits = _logits(rng, 2, 3)
        top2 = np.asarray(jnp.argsort(logits, -1))[:, :, -2:]
        drafts = jnp.zeros((2, 3), jnp.int32)
        for seed in range(5):
            _, _, free = spec_sample(
                logits, drafts,
                jnp.full((2,), 1.0), jnp.full((2,), 2, jnp.int32), jnp.ones((2,)),
                jax.random.PRNGKey(seed),
            )
            f = np.asarray(free)
            for bi in range(2):
                for si in range(3):
                    assert f[bi, si] in top2[bi, si]


class TestSampleTokensStillIntact:
    def test_greedy_and_sampled(self):
        rng = np.random.default_rng(5)
        logits = jnp.asarray(rng.standard_normal((4, V)) * 3, jnp.float32)
        toks = sample_tokens(
            logits,
            jnp.asarray([0.0, 0.0, 1.0, 1.0]),
            jnp.asarray([0, 0, 2, 0], jnp.int32),
            jnp.asarray([1.0, 1.0, 1.0, 0.9]),
            jax.random.PRNGKey(0),
        )
        toks = np.asarray(toks)
        argmax = np.asarray(jnp.argmax(logits, -1))
        assert toks[0] == argmax[0] and toks[1] == argmax[1]
        assert all(0 <= t < V for t in toks)


# -- the gate: no vocabulary sort in a dispatch where no lane samples ---------------
#
# The oracle is the sampler as it was before the gate, copied here whole:
# the gated functions must return what these return, bit for bit, for the
# same key.


def _oracle_filtered_logits(logits, temperature, top_k, top_p):
    vocab = logits.shape[-1]
    safe_t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / safe_t
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.where(top_k > 0, top_k, vocab).astype(jnp.int32)
    kth_val = jnp.take_along_axis(
        sorted_desc, jnp.clip(k - 1, 0, vocab - 1)[:, None], axis=-1
    )
    masked = jnp.where(scaled >= kth_val, scaled, -jnp.inf)
    sorted_masked = jnp.sort(masked, axis=-1)[:, ::-1]
    probs_sorted = jax.nn.softmax(sorted_masked, axis=-1)
    cumprobs = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_mask = (cumprobs - probs_sorted) < top_p[:, None]
    threshold = jnp.min(
        jnp.where(cutoff_mask, sorted_masked, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(masked >= threshold, masked, -jnp.inf)


@jax.jit
def _oracle_sample_tokens(logits, temperature, top_k, top_p, rng_key):
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _oracle_filtered_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng_key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


@jax.jit
def _oracle_spec_sample(logits, drafts, temperature, top_k, top_p, rng_key):
    b, s, vocab = logits.shape
    flat = logits.reshape(b * s, vocab)
    rep = lambda x: jnp.repeat(x, s)
    masked = _oracle_filtered_logits(
        flat, rep(temperature), rep(top_k), rep(top_p)
    )
    greedy = jnp.argmax(flat, axis=-1).astype(jnp.int32)
    d = drafts.reshape(-1).astype(jnp.int32)
    probs = jax.nn.softmax(masked, axis=-1)
    p_draft = jnp.take_along_axis(probs, d[:, None], axis=-1)[:, 0]
    k_u, k_repl, k_free = jax.random.split(rng_key, 3)
    u = jax.random.uniform(k_u, (b * s,))
    accept = jnp.where(rep(temperature) > 0, u < p_draft, d == greedy)
    draft_hot = jax.nn.one_hot(d, vocab, dtype=bool)
    masked_no_draft = jnp.where(draft_hot, -jnp.inf, masked)
    repl_sampled = jax.random.categorical(k_repl, masked_no_draft, axis=-1)
    replacement = jnp.where(
        rep(temperature) > 0, repl_sampled, greedy
    ).astype(jnp.int32)
    free_sampled = jax.random.categorical(k_free, masked, axis=-1)
    free = jnp.where(rep(temperature) > 0, free_sampled, greedy).astype(
        jnp.int32
    )
    return accept.reshape(b, s), replacement.reshape(b, s), free.reshape(b, s)


LANES, WIDE = 16, 512
SAMPLED_LANES = {"all_greedy": (), "one_of_16": (5,), "all_sampled": range(LANES)}


def _lane_params(lanes, filtered):
    """Per-lane sampling parameters: ``lanes`` sample, the rest are greedy."""
    temperature = np.zeros((LANES,), np.float32)
    temperature[list(lanes)] = 0.7 + 0.05 * np.arange(len(lanes))
    top_k = np.zeros((LANES,), np.int32)
    top_p = np.ones((LANES,), np.float32)
    if filtered:
        top_k[::2] = 7  # greedy lanes carry filters too: they must stay inert
        top_p[1::3] = 0.8
    return jnp.asarray(temperature), jnp.asarray(top_k), jnp.asarray(top_p)


@pytest.mark.parametrize("filtered", [False, True], ids=["plain", "topk_topp"])
@pytest.mark.parametrize("lanes", SAMPLED_LANES)
class TestGateIsBitIdenticalToTheUngatedSampler:
    def test_sample_tokens(self, lanes, filtered):
        rng = np.random.default_rng(11)
        logits = jnp.asarray(rng.standard_normal((LANES, WIDE)) * 3, jnp.float32)
        params = _lane_params(SAMPLED_LANES[lanes], filtered)
        for seed in (0, 1, 2**31 + 7):
            key = jax.random.PRNGKey(seed)
            np.testing.assert_array_equal(
                np.asarray(sample_tokens(logits, *params, key)),
                np.asarray(_oracle_sample_tokens(logits, *params, key)),
            )

    def test_spec_sample(self, lanes, filtered):
        rng = np.random.default_rng(12)
        logits = jnp.asarray(
            rng.standard_normal((LANES, 3, WIDE)) * 3, jnp.float32
        )
        # half the drafts are the argmax, so greedy lanes accept and reject
        drafts = np.array(jnp.argmax(logits, -1))
        drafts[:, 1] = (drafts[:, 1] + 1) % WIDE
        drafts = jnp.asarray(drafts, jnp.int32)
        params = _lane_params(SAMPLED_LANES[lanes], filtered)
        for seed in (0, 3):
            key = jax.random.PRNGKey(seed)
            got = spec_sample(logits, drafts, *params, key)
            want = _oracle_spec_sample(logits, drafts, *params, key)
            for g, w in zip(got, want):
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# What the filter is made of, and nothing else in these programs is: a
# program that holds one of these outside the sampled branch pays for the
# whole vocabulary in every greedy dispatch.
FILTER_PRIMITIVES = {"sort", "cumsum"}


def _walk(jaxpr, where=(), closed=()):
    """Every equation of ``jaxpr`` and of the programs nested in it, each
    with the cond branches it sits under (a tuple of branch indices; 0 is
    the false branch). The primitives named in ``closed`` are not looked
    into."""
    for eqn in jaxpr.eqns:
        yield eqn, where
        if eqn.primitive.name in closed:
            continue
        if eqn.primitive.name == "cond":
            for i, branch in enumerate(eqn.params["branches"]):
                yield from _walk(branch.jaxpr, where + (i,), closed)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, where, closed)


def _assert_filter_is_gated(closed_jaxpr):
    found = list(_walk(closed_jaxpr.jaxpr))
    assert any(eqn.primitive.name == "cond" for eqn, _ in found)
    filters = [
        (eqn.primitive.name, where) for eqn, where in found
        if eqn.primitive.name in FILTER_PRIMITIVES
    ]
    assert {name for name, _ in filters} == FILTER_PRIMITIVES
    # inside the sampled (true) branch of the one cond, none elsewhere
    assert all(where == (1,) for _, where in filters), filters
    return found


class TestTheFilterSitsInsideTheSampledBranch:
    def test_sample_tokens(self):
        params = _lane_params((), True)
        _assert_filter_is_gated(
            jax.make_jaxpr(sample_tokens)(
                jnp.zeros((LANES, WIDE)), *params, jax.random.PRNGKey(0)
            )
        )

    def test_spec_sample(self):
        params = _lane_params((), True)
        _assert_filter_is_gated(
            jax.make_jaxpr(spec_sample)(
                jnp.zeros((LANES, 3, WIDE)), jnp.zeros((LANES, 3), jnp.int32),
                *params, jax.random.PRNGKey(0),
            )
        )

    @pytest.mark.parametrize("num_steps", [1, 4])
    def test_decode_steps(self, num_steps):
        args, kw = _decode_args((), num_steps)
        closed_jaxpr = jax.make_jaxpr(
            functools.partial(llama.decode_steps, **kw), static_argnums=(1,),
        )(*args)
        found = _assert_filter_is_gated(closed_jaxpr)
        # the burst's keys are split where they were: outside the gate
        splits = [w for eqn, w in found if eqn.primitive.name == "random_split"]
        assert splits and all(where == () for where in splits)
        # and the KV pools pass through no cond of the program (a kernel's
        # own ``pl.when``s name the pool it walks in place, by reference:
        # they are the kernel's, and no branch of the program's)
        pool = args[4].shape
        conds = 0
        for eqn, _ in _walk(closed_jaxpr.jaxpr, closed=("pallas_call",)):
            if eqn.primitive.name == "cond":
                conds += 1
                assert all(v.aval.shape != pool for v in eqn.invars)
        assert conds

    def test_a_sort_outside_the_gate_is_found(self):
        def leaky(logits, *rest):
            floor = jnp.sort(logits, axis=-1)[:, 0]
            return sample_tokens(logits, *rest) + (floor > 0)

        params = _lane_params((), True)
        with pytest.raises(AssertionError, match="sort"):
            _assert_filter_is_gated(
                jax.make_jaxpr(leaky)(
                    jnp.zeros((LANES, WIDE)), *params, jax.random.PRNGKey(0)
                )
            )


DECODE_LANES, DECODE_PAGE = 4, 4


def _decode_args(sampled_lanes, num_steps, seed=0):
    """``llama.decode_steps``' arguments on the tiny model: four lanes, each
    with a context of a few random tokens' worth of (zero) KV."""
    cfg = llama.TINY_LLAMA
    params = _tiny_params()
    pages_per_seq = 4
    k_pages, v_pages = llama.init_kv_pages(
        cfg, DECODE_LANES * pages_per_seq + 1, DECODE_PAGE
    )
    block_tables = (
        np.arange(DECODE_LANES * pages_per_seq).reshape(DECODE_LANES, -1) + 1
    )
    temperature = np.zeros((DECODE_LANES,), np.float32)
    temperature[list(sampled_lanes)] = 1.0
    args = (
        params, cfg,
        jnp.asarray([3, 5, 7, 11], jnp.int32),  # tokens
        jnp.asarray(llama.pack_decode_inputs(
            np.asarray([2, 3, 4, 5]),  # positions
            block_tables,
            np.asarray([3, 4, 5, 6]),  # seq_lens
            temperature,
            np.zeros((DECODE_LANES,), np.int32),
            np.ones((DECODE_LANES,), np.float32),
        )),
        k_pages, v_pages,
        jax.random.PRNGKey(seed),
    )
    return args, dict(page_size=DECODE_PAGE, num_steps=num_steps, interpret=True)


@functools.lru_cache(maxsize=1)
def _tiny_params():
    return llama.init_params(jax.random.PRNGKey(0), llama.TINY_LLAMA)


@pytest.mark.parametrize(
    "sampled_lanes", [(), (2,), (0, 1, 2, 3)],
    ids=["all_greedy", "one_sampled", "all_sampled"],
)
def test_decode_steps_one_and_four_start_the_same_stream(sampled_lanes):
    """``decode_steps``' promise at its ``num_steps == 1`` branch: the plain
    body call consumes the key the scan's first slice would, so for one key
    the burst of one emits the first token of the burst of four."""
    for seed in (0, 1, 2):
        args1, kw1 = _decode_args(sampled_lanes, 1, seed)
        args4, kw4 = _decode_args(sampled_lanes, 4, seed)
        # a burst is [count of experts read | tokens]; the dense model reads none
        one = np.asarray(llama.decode_steps(*args1, **kw1)[0])
        four = np.asarray(llama.decode_steps(*args4, **kw4)[0])
        assert not one[:, 0].any() and not four[:, 0].any()
        one, four = one[:, 1:], four[:, 1:]
        assert one.shape == (DECODE_LANES, 1) and four.shape == (DECODE_LANES, 4)
        np.testing.assert_array_equal(one[:, 0], four[:, 0])
    # a sampled lane does sample: over the seeds its stream is not one token
    if sampled_lanes:
        lane = sampled_lanes[0]
        firsts = {
            int(np.asarray(
                llama.decode_steps(*a, **k)[0]
            )[lane, 1])
            for a, k in (_decode_args(sampled_lanes, 1, s) for s in range(8))
        }
        assert len(firsts) > 1
