"""The device's timeline names the model's parts (``llama.MODEL_SCOPES``).

Every operation the model traces lies under ``jax.named_scope("model.<part>")``
in every program, because the scopes stand in the helpers and the two layer
loops the programs share. A scope is the ``op_name`` of the lowered
instruction, so what is held here is read from the lowered text: each program
of each kind of model carries exactly the names its layers have, and no name
outside the one tuple stands anywhere in the package."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    TINY_LFM2_MOE,
    TINY_LLAMA,
    TINY_MLA_MOE,
    TINY_QWEN3_MOE,
    TINY_SCMOE,
    TINY_SDAR_MOE,
    TINY_LING_HYBRID,
    TINY_SMALLTHINKER,
    TINY_SOLAR_HYBRID,
    TINY_SWA_MOE,
    llama,
)
from llm_d_kv_cache_manager_tpu.ops import sampling

PS, PAGES, LANES, TABLE_W, CHUNK = 4, 16, 2, 4, 8

#: a GQA model whose first layer is dense and whose routed layers have a
#: shared expert beside the routed ones
TINY_ROUTED = dataclasses.replace(
    TINY_QWEN3_MOE, n_layers=3, first_k_dense=1, n_shared_experts=1
)

EVERY = {"attn", "cache_write", "head"}
ROUTED = {"ffn", "moe_router", "moe_experts", "moe_shared"}
CONFIGS = {
    "dense": (TINY_LLAMA, EVERY | {"ffn"}),
    "routed": (TINY_ROUTED, EVERY | ROUTED),
    "latent": (TINY_MLA_MOE, EVERY | ROUTED),
    "conv": (TINY_LFM2_MOE, EVERY | ROUTED - {"moe_shared"} | {"conv"}),
    "blocks": (TINY_SDAR_MOE, EVERY | {"moe_router", "moe_experts"}),
    # the double layer: both attentions with the query's two matmuls under
    # ``attn``, both dense FFNs under ``ffn``, the identity experts' own
    "double": (TINY_SCMOE, EVERY | ROUTED - {"moe_shared"} | {"moe_zero"}),
    # sliding layers under a scope of their own beside the one full layer's
    "window": (TINY_SWA_MOE, EVERY | ROUTED | {"attn_window"}),
    # linear layers (the whole mixer under ``kda``) beside the latent ones
    "linear": (TINY_LING_HYBRID, EVERY | ROUTED | {"kda"}),
    # every layer routed, its gates made ahead of its attention under a
    # scope of their own: no dense FFN, no shared expert
    "prerouted": (TINY_SMALLTHINKER, EVERY | {
        "attn_window", "moe_preroute", "moe_router", "moe_experts"}),
    # linear layers beside a gated GQA layer in one program: the low-rank
    # pairs, the kernel and the gated norm under ``kda``, the GQA gate under
    # ``attn``; every layer routed beside a shared expert (one period)
    "linear_gqa": (dataclasses.replace(TINY_SOLAR_HYBRID, n_layers=4),
                   EVERY | ROUTED - {"ffn"} | {"kda"}),
}


def _shapes(cfg):
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
    )
    k_pages, v_pages = jax.eval_shape(
        lambda: llama.init_kv_pages(cfg, PAGES, PS)
    )
    state = jax.eval_shape(lambda: llama.init_state_pages(cfg, PAGES))
    window = jax.eval_shape(lambda: llama.init_window_pages(cfg, PAGES, PS))
    if window is not None:  # the pair of window pools; the tables ride apart
        return params, k_pages, v_pages, {"window_pages": window}
    slots = jax.eval_shape(lambda: llama.init_kda_state(cfg, PAGES))
    if slots is not None:  # the state pool of slots; the rows' slots apart
        return params, k_pages, v_pages, {"state_pages": slots}
    return params, k_pages, v_pages, (
        {} if state is None else {"state_pages": state}
    )


def _window_tables(state, width):
    """A window model's second packed operand (``width`` columns of page
    ids before the table), for the programs that take one."""
    if isinstance(state.get("state_pages"), tuple):
        # a linear model's second operand: its rows' slots ([read, write] a
        # prefill row, [a, b, switch] a decode lane)
        return {"state_slots": jnp.zeros((LANES, 2 if width else 3), jnp.int32)}
    if "window_pages" not in state:
        return {}
    return {"window_packed": jnp.zeros((LANES, width + TABLE_W + 1), jnp.int32)}


def _scopes_in(lowered_text: str) -> frozenset:
    # an op_name is a path of scopes; inside a scan's body it starts anew
    return frozenset(re.findall(r'["/]model\.([a-z_]+)/', lowered_text))


@functools.lru_cache(maxsize=None)
def _lowered_scopes(cfg, program) -> frozenset:
    params, k_pages, v_pages, state = _shapes(cfg)
    key = jax.random.PRNGKey(0)
    if program == "decode_steps":
        lowered = llama.decode_steps.lower(
            params, cfg, jnp.zeros((LANES, 3), jnp.int32),
            jnp.zeros((LANES, TABLE_W + llama.DECODE_PACKED_TAIL), jnp.int32),
            k_pages, v_pages, key, page_size=PS, num_steps=2, interpret=True,
            **state, **_window_tables(state, 0),
        )
    elif program == "prefill":
        lowered = llama.prefill_packed.lower(
            params, cfg,
            jnp.zeros((LANES, 5 * CHUNK + TABLE_W + 1), jnp.int32),
            k_pages, v_pages, chunk=CHUNK, attn_impl="xla", interpret=True,
            **state, **_window_tables(state, CHUNK),
        )
    elif program == "denoise_steps":
        width = cfg.block_length
        lowered = llama.denoise_steps.lower(
            params, cfg,
            jnp.zeros((LANES, 2 * width + TABLE_W + 5), jnp.int32),
            jnp.zeros((LANES, 3), jnp.float32), k_pages, v_pages, key,
            page_size=PS, table_w=TABLE_W, attn_impl="xla", interpret=True,
        )
    else:
        raise AssertionError(program)
    return _scopes_in(lowered.as_text(debug_info=True))


CASES = [
    (kind, program)
    for kind in ("dense", "routed", "latent", "conv", "double", "window",
                 "linear", "prerouted", "linear_gqa")
    for program in ("decode_steps", "prefill")
] + [("blocks", "prefill"), ("blocks", "denoise_steps")]


@pytest.mark.parametrize("kind,program", CASES,
                         ids=[f"{k}-{p}" for k, p in CASES])
def test_a_program_carries_the_scopes_its_layers_have_and_no_other(
        kind, program):
    cfg, want = CONFIGS[kind]
    # the sampler runs inside the decode-side programs; a prefill's first
    # tokens are sampled by a program of their own
    if program != "prefill":
        want = want | {"sample"}
    got = _lowered_scopes(cfg, program)
    assert got <= set(llama.MODEL_SCOPES), got - set(llama.MODEL_SCOPES)
    assert got == want, (sorted(got - want), sorted(want - got))


@pytest.mark.parametrize("kind", list(CONFIGS))
def test_the_rows_loop_of_a_prefill_program_carries_its_scopes(kind):
    """An eight-row ``prefill_packed`` holds its forward in a loop over the
    rows: the loop's body carries every part the layers have, the one write
    of the pools comes after it, and the module keeps ``prefill`` in its
    name (the benchmark's readers of ``prefill_scope_ms`` find it by that)."""
    cfg, want = CONFIGS[kind]
    params, k_pages, v_pages, state = _shapes(cfg)
    lowered = llama.prefill_packed.lower(
        params, cfg, jnp.zeros((8, 5 * CHUNK + TABLE_W + 1), jnp.int32),
        k_pages, v_pages, chunk=CHUNK, attn_impl="xla", interpret=True,
        **state, **{k: jnp.zeros((8, v.shape[1]), jnp.int32)
                    for k, v in _window_tables(state, CHUNK).items()},
    )
    text = lowered.as_text(debug_info=True)
    assert "@jit_prefill_packed" in text
    assert "stablehlo.while" in text and "call @_prefill_rows" in text
    # the row's forward is a function of its own (``llama._prefill_rows``),
    # called from the loop's body: its operations' paths start anew
    names = set(re.findall(r'"([^"]*?)model\.([a-z_]+)/', text))
    assert {part for path, part in names if not path} == want - {"cache_write"}
    assert {part for path, part in names if path} == {"cache_write"}
    assert {path for path, _ in names} == {"", "jit(prefill_packed)/"}


def test_the_first_tokens_sampler_is_under_the_sample_scope():
    text = sampling.sample_tokens_packed.lower(
        jnp.zeros((LANES, 64), jnp.bfloat16),
        jnp.zeros((LANES, 3), jnp.int32), jax.random.PRNGKey(0),
    ).as_text(debug_info=True)
    assert _scopes_in(text) == {"sample"}


def test_model_scopes_is_the_one_list_of_names():
    """Every name of the tuple stands in some program, the programs carry no
    other (each case above), and the sampler's file opens the tuple's last."""
    carried = set().union(
        *(_lowered_scopes(CONFIGS[kind][0], program) for kind, program in CASES)
    )
    assert carried == set(llama.MODEL_SCOPES)
    assert len(set(llama.MODEL_SCOPES)) == len(llama.MODEL_SCOPES)
    assert llama.MODEL_SCOPES[-1] == "sample"
