"""A new architecture comes into the benchmark as files and entries: a
reference found by name (with its own system side and tolerances), published
widths a configuration lists itself, and request parameters a traffic mix
states as data. CPU only; the rehearsals drive the whole run in this process
with throw-away files, and no file that is there is edited.
"""

import asyncio
import contextlib
import json
import os
import types

import pytest

from chipbench import gateway as gw
from chipbench import reference, run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASE = os.path.join(ROOT, "chipbench")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

#: a reference written out for the test: its own attention (so its own
#: mask), FFN, tolerances and system side
THROWAWAY_REFERENCE = '''
"""Throw-away reference of the tests: a dense decoder with its own mask."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as common

TOL_BF16 = {"max": 2e-2, "p50": 1e-2}


def _attention(layer, cfg, x):
    f32 = jnp.float32
    s, hd = x.shape[0], cfg.hd
    q = (x @ layer["wq"].astype(f32)).reshape(s, cfg.n_heads, hd)
    k = (x @ layer["wk"].astype(f32)).reshape(s, cfg.n_kv_heads, hd)
    v = (x @ layer["wv"].astype(f32)).reshape(s, cfg.n_kv_heads, hd)
    pos = jnp.arange(s)
    q = common._rope(q, pos, cfg.rope_theta)
    k = common._rope(k, pos, cfg.rope_theta)
    k = jnp.repeat(k, cfg.n_heads // cfg.n_kv_heads, axis=1)
    v = jnp.repeat(v, cfg.n_heads // cfg.n_kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, cfg.n_heads * hd) @ layer["wo"].astype(f32)


def _ffn(layer, cfg, x):
    f32 = jnp.float32
    out = common._swiglu(x, layer["w_gate"].astype(f32),
                         layer["w_up"].astype(f32), layer["w_down"].astype(f32))
    return out, jnp.full(x.shape[0], jnp.inf, f32)


def forward(params, cfg, tokens):
    return common.decoder_forward(params, cfg, tokens, _ffn, _attention)[0], None


def system(engine, tokens, steps, interpret, params=None, cfg=None):
    print("[throwaway] the reference's own system side", flush=True)
    return common.system_logits(engine, tokens, steps, interpret, params, cfg)


def check(*args, **kwargs):  # no file brings its own pass: never called
    return {"ok": True}
'''
CAUSAL_MASK = "jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)"

CONFIG = {
    "source": "test", "chipbench": {
        "model_name": "tiny-llama", "preset": "TINY_LLAMA", "replace": {},
        "reference": None,  # throwaway_cell names its own, by tag
        "env": {"BLOCK_SIZE": 4, "TOTAL_PAGES": 256, "MAX_MODEL_LEN": 128,
                "DECODE_BATCH_SIZE": 2},
        "engine": {"prefill_bucket": 16, "decode_pages_bucket": 8}}}
MIX = {
    "kind": "closed", "callers_per_lane": 2, "requests": 64, "sizes_seed": 9,
    "groups": None, "unique": {"dist": "uniform", "min": 4, "max": 12},
    "output": {"dist": "uniform", "min": 3, "max": 6}}


@contextlib.contextmanager
def throwaway_cell(tag, reference_src=None, config=CONFIG, mix=MIX, per_layer=()):
    """Files and entries of one more cell, written and removed again; yields
    the argv that runs it."""
    config_name, mix_name = f"throwaway-{tag}", f"throwaway-{tag}-mix"
    cell = f"{config_name}.{mix_name}"
    files = {os.path.join(BASE, "traffic", mix_name + ".json"): json.dumps(mix)}
    if reference_src is not None:
        # by tag, so that no two cases write the same file
        config = json.loads(json.dumps(config))
        config["chipbench"]["reference"] = config_name
        files[os.path.join(BASE, "references", config_name + ".py")] = reference_src
    files[os.path.join(BASE, "configs", config_name + ".json")] = json.dumps(config)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": config_name, "source": "test", "reduced": [], "why": "test",
        "file": f"chipbench/configs/{config_name}.json"})
    bench["workloads"].append({"name": cell, "config": config_name,
                               "traffic": mix_name, "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in per_layer:
            m["workloads"] = m.get("workloads", []) + [cell]
    bench_path = os.path.join(BASE, "out", f"throwaway_{tag}_benchmark.json")
    os.makedirs(os.path.dirname(bench_path), exist_ok=True)
    files[bench_path] = json.dumps(bench)
    try:
        for path, content in files.items():
            with open(path, "w") as f:
                f.write(content)
        yield ["--workload", cell, "--seconds", "2", "--rehearse",
               "--benchmark", bench_path]
    finally:
        for path in files:
            if os.path.exists(path):
                os.remove(path)


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


# -- a reference is a file found by name ----------------------------------------
@pytest.mark.parametrize("wrong", [False, True], ids=["as-described", "mask-dropped"])
def test_a_reference_comes_as_a_file_and_decides_correct_both_ways(wrong, capsys):
    """Its own ``forward``, ``TOL_BF16`` and ``system``, named by a
    throw-away configuration; with the causal mask dropped from its forward
    the same run is not correct, and says by how much: the comparison is the
    harness's, whatever else the file defines."""
    assert CAUSAL_MASK in THROWAWAY_REFERENCE
    src = THROWAWAY_REFERENCE.replace(CAUSAL_MASK, "scores") if wrong else THROWAWAY_REFERENCE
    tag = "ref-wrong" if wrong else "ref"
    with throwaway_cell(tag, reference_src=src) as argv:
        assert run.main(argv + ["--seed", "7", "--trace", "0"]) == 0
    line, out = last_line(capsys)
    ref = line["reference"]
    assert line["correct"] is (not wrong) and ref["ok"] is (not wrong)
    assert line["failed"] == 0 and line["attempted"] > 3
    # the module's own bounds (here at f32: its keys) and its own system side
    assert set(ref["tol"]) == {"max", "p50"} and "layer_rel_err" not in ref
    assert (ref["rel_err"] > ref["tol"]["max"]) is wrong
    assert sum("the reference's own system side" in l for l in out) == ref["sequences"]
    assert not os.path.exists(os.path.join(BASE, "references", f"throwaway-{tag}.py"))


@pytest.mark.parametrize("call", [
    lambda: reference.check(None, "no-such-reference", 1, True),
    lambda: reference.forward({}, None, [1], "no-such-reference"),
    lambda: reference.forward_with_gaps({}, None, [1], "no-such-reference"),
], ids=["check", "forward", "forward_with_gaps"])
def test_an_unknown_reference_fails_with_the_path_looked_for(call):
    path = os.path.join(BASE, "references", "no-such-reference.py")
    with pytest.raises(run.BenchFailure) as e:
        call()
    assert path in str(e.value)


def test_a_configuration_that_names_no_file_ends_the_run(capsys):
    config = json.loads(json.dumps(CONFIG))
    config["chipbench"]["reference"] = "nowhere"
    with throwaway_cell("noref", config=config) as argv:
        with pytest.raises(run.BenchFailure, match="references/nowhere.py"):
            run.main(argv + ["--seed", "1"])
    assert not [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]


@pytest.mark.parametrize("name", sorted(
    f[:-3] for f in os.listdir(os.path.join(BASE, "references"))
    if f.endswith(".py") and not f.startswith("throwaway")))  # a killed run's leftover
def test_every_reference_in_the_tree_keeps_the_contract(name):
    ref = reference.load(name)
    assert callable(ref.forward) and {"max", "p50"} <= set(ref.TOL_BF16)
    assert ref.__doc__ and "olerance" in ref.__doc__
    if "layer_p75" in ref.TOL_BF16:
        assert 0 < ref.ROUTER_GAP_MIN
    used = {c["name"] for c in BENCH["configs"]
            if run.load_config(c["name"])["reference"] == name}
    assert used, "a reference no configuration names"


def test_the_references_state_their_limits():
    """``dense`` as ``reference.py`` had it in one table at the parent; ``moe``
    as PR 27's readings set it (its docstring): the layers alone are held by
    a quartile that routing swaps do not move, the whole model loosely."""
    dense, moe = reference.load("dense"), reference.load("moe")
    assert dense.TOL_BF16 == {"max": 3e-2, "p50": 1.25e-2}
    assert moe.TOL_BF16 == {"max": 0.3, "p50": 0.15, "layer_p75": 1.1e-2}
    assert moe.ROUTER_GAP_MIN == 0.05
    assert (reference.PROMPT_TOKENS, reference.DECODE_STEPS, reference.SEQUENCES,
            reference.HEAD_BLOCKS, reference.TOL_F32) == (128, 8, 2, 8, 2e-4)


# -- the width check reads its list from the configuration ------------------------
def tiny_config(widths=None, **published):
    from llm_d_kv_cache_manager_tpu import models

    cfg = models.TINY_LLAMA
    pub = {
        "hidden_size": cfg.hidden_size, "head_dim": cfg.hd,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "intermediate_size": cfg.intermediate_size, **published,
    }
    config = {"preset": "TINY_LLAMA", "replace": {}, "published": pub}
    if widths is not None:
        config["widths"] = widths
    return config


@pytest.mark.parametrize("widths,published,fails", [
    (None, {}, None),
    (None, {"hidden_act": "gelu"}, None),  # unlisted: checked by nothing, as today
    ({"hidden_act": "hidden_act", "qk_norm": "qk_norm"},
     {"hidden_act": "silu", "qk_norm": False}, None),
    ({"hidden_act": "hidden_act"}, {"hidden_act": "gelu"}, "hidden_act"),
    ({"hidden_act": "hidden_act", "qk_norm": "qk_norm"}, {"hidden_act": "silu"}, "qk_norm"),
    ({"sliding_window": "sliding_window"}, {"sliding_window": 4096}, "sliding_window"),
    ({"hidden_size": "intermediate_size"}, {}, "hidden_size"),  # a listed key replaces the built-in
], ids=["no-widths", "unlisted-key", "agree", "disagree", "missing-in-file",
        "missing-in-preset", "over-a-built-in"])
def test_widths_a_configuration_lists_are_checked(widths, published, fails):
    config = tiny_config(widths, **published)
    if fails is None:
        assert run.model_config(config, rehearse=False).hidden_size == 64
    else:
        with pytest.raises(run.BenchFailure, match=repr(fails)):
            run.model_config(config, rehearse=False)
    run.model_config(config, rehearse=True)  # a rehearsal's tiny preset is not held


# -- a mix states request parameters as data ----------------------------------------
SAMPLED = {"temperature": 0.8, "top_k": 40}


def window(mix, seed=3):
    return traffic.build_schedule(mix, seed, 5.0, pods=1,
                                  pool_tokens_per_pod=1024, lanes=2).requests


@pytest.mark.parametrize("share,lo,hi", [(None, 64, 64), (1.0, 64, 64),
                                         (0.5, 22, 42), (0.0, 0, 0)])
def test_request_parameters_are_drawn_from_the_mix(share, lo, hi):
    mix = {**MIX, "request": SAMPLED}
    if share is not None:
        mix["request_share"] = share
    reqs = window(mix)
    carrying = [r for r in reqs if r.params is not None]
    assert lo <= len(carrying) <= hi and all(r.params == SAMPLED for r in carrying)
    # every seed sends the same, and the sizes are those of the mix without it
    assert [r.params for r in window(mix, seed=2**31 + 9)] == [r.params for r in reqs]
    plain = window(MIX)
    assert all(r.params is None for r in plain)
    assert [(r.prompt, r.max_tokens) for r in plain] == [(r.prompt, r.max_tokens) for r in reqs]
    assert traffic.request_params(mix, 5, 7) == traffic.request_params(mix, 5, 7)


@pytest.mark.parametrize("key", ["prompt", "max_tokens"])
def test_a_request_may_not_state_what_the_schedule_draws(key):
    """Else the shapes warmed and ``out_tokens_per_s`` would not follow the
    schedule."""
    mix = {**MIX, "request": {**SAMPLED, key: 1}}
    with pytest.raises(run.BenchFailure, match=key):
        window(mix)
    with pytest.raises(run.BenchFailure, match=key):
        traffic.request_params(mix, 3, 7)


@pytest.mark.parametrize("params,want", [
    (None, '{"prompt": "abc", "max_tokens": 5, "temperature": 0.0}'),
    (SAMPLED, '{"prompt": "abc", "max_tokens": 5, "temperature": 0.8, "top_k": 40}'),
], ids=["as-today", "sampled"])
def test_the_body_posted_to_the_pod(params, want):
    """Without ``request`` the body is byte for byte the three keys of today."""
    posted = []
    gateway = gw.Gateway("http://scorer", [types.SimpleNamespace(name="p0", url="http://p0")],
                         "m", capacity_blocks=16)

    async def post(url, body):
        posted.append((url, json.dumps(body)))
        return 200, {"scores": {}}

    gateway._post = post
    req = traffic.Request(index=0, due_s=None, group=None, prefix_len=0,
                          prompt="abc", max_tokens=5, params=params)
    rec = asyncio.run(gateway.complete(req, 0.0, lambda: 0.0))
    assert rec["status"] == 200 and rec["error"] is None
    assert posted == [("http://scorer/score_completions", '{"prompt": "abc", "model": "m"}'),
                      ("http://p0/v1/completions", want)]


@pytest.fixture
def bodies(monkeypatch):
    """Every body a run posts to a pod's ``/v1/completions``, as sent."""
    posted = []
    real_post = gw.Gateway._post

    async def post(self, url, body):
        if url.endswith("/v1/completions"):
            posted.append(json.dumps(body))
        return await real_post(self, url, body)

    monkeypatch.setattr(gw.Gateway, "_post", post)
    return posted


def test_a_mix_with_request_parameters_reaches_the_sampler(capsys, bodies):
    """The rehearsal pod counts dispatches with a sampled lane; with
    ``request_share`` 0.5 about half of what warm-up and window post carries
    the parameters, and the rest is today's three keys, byte for byte."""
    mix = {**MIX, "request": SAMPLED, "request_share": 0.5}
    config = json.loads(json.dumps(CONFIG))
    config["chipbench"]["reference"] = "dense"
    with throwaway_cell("sampled", config=config, mix=mix,
                        per_layer=("sampled_dispatch_share",)) as argv:
        assert run.main(argv + ["--seed", "11", "--trace", "1"]) == 0
    line, _ = last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert 0 < line["metrics"]["sampled_dispatch_share"]["value"] <= 100
    carrying = [b for b in bodies if "top_k" in b]
    assert len(bodies) > 10 and 0.25 < len(carrying) / len(bodies) < 0.75
    for b in bodies:
        d = json.loads(b)
        want = {"prompt": d["prompt"], "max_tokens": d["max_tokens"], "temperature": 0.0}
        if b in carrying:
            want.update(SAMPLED)
        assert b == json.dumps(want)


def test_the_cells_own_mixes_state_no_request():
    """So both cells post ``prompt``, ``max_tokens`` and ``temperature: 0.0``
    (``test_the_body_posted_to_the_pod[as-today]``) and no lane samples."""
    mixes = {w["traffic"] for w in BENCH["workloads"]}
    assert {"sessions", "reasoning"} <= mixes  # the two it was written for
    for name in ("sessions", "reasoning"):  # a later cell's mix may state one
        spec = traffic.load_traffic(name)
        assert "request" not in spec and "request_share" not in spec
        assert traffic.request_params(spec, 3, 6) == [None] * 3
