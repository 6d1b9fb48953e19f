"""Llama-family decoder, pure-functional JAX with paged KV cache.

Design (TPU-first, not a torch translation):

- Parameters are a plain pytree of arrays — directly shardable with
  ``jax.sharding`` (see ``parallel/sharding.py`` for the tp/dp rules).
- Two jitted entry points match the serving engine's phases:
  ``prefill`` (chunk of tokens, writes KV into assigned pages, returns
  last-position logits) and ``decode_step`` (one token per sequence via the
  Pallas paged-attention kernel).
- KV pages are function inputs/outputs (donated by the engine) with layout
  ``[n_layers, total_pages, page_size, n_kv_heads, head_dim]`` — page-major
  with (n_kv, head_dim) minor-contiguous, so a page's full KV tile is one
  contiguous block for the decode kernel AND the per-token write slice is
  contiguous for the scatter (a head-major pool forced full-pool
  layout-conversion copies around the Pallas call). Whether the compiled
  program keeps the pool in that default layout end to end is the TPU
  compiler's decision, not this file's: on a v5e it did not while the
  all-layer scatter had the layer axis in its window (four whole-pool
  copies a program with fewer than 8 KV heads: PERF_LEDGER.jsonl, PR 28).
  ``_scatter_kv_pages_all_layers`` now indexes flat token rows, both
  attention kernels take the five-dimensional pool with the layer in their
  own page addressing (no ``k_pages[li]`` slice on the Pallas path), and
  ``tests/test_pool_layout.py`` reads the programs compiled for the chip;
  ``python -m tools.aot_pool_copies`` lists every instruction shaped like
  the pool or a layer of it.
- Weights default to bfloat16 (MXU-native); attention/softmax accumulate in
  float32.

The architecture covers Llama 2/3 and Qwen-style GQA decoders (RMSNorm,
RoPE, SwiGLU, optional QKV biases, optional tied embeddings),
Mixtral-style sparse-MoE decoders (``n_experts > 0``: softmax-top-k routed
SwiGLU experts replacing the dense FFN; attention/KV paths are identical,
so paged serving and prefix-cache routing work unchanged), and the Gemma
family (gated-GELU FFN, ``(1+w)`` RMSNorm scaling, sqrt(d)-scaled tied
embeddings, decoupled head_dim), and DeepSeek-V3-style decoders
(``kv_lora_rank > 0``: latent attention over ONE pool of latent rows
``[n_layers, total_pages, page_size, row]`` and no value pool, absorbed
for decode and warm prefill, ``ops/mla_attention.py``; shared experts, a
sigmoid router with a correction bias, leading dense layers). The page
manager, KV events and routing know tokens, not heads, and serve all of
them alike.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import (
    apply_rope,
    paged_attention,
    prefill_with_paged_context,
    rms_norm,
    rope_frequencies,
)
from ..ops.rope import RopeScalingConfig
from ..ops.sampling import (
    block_candidates,
    block_transfer,
    pack_sampling_params,
    sample_tokens,
    spec_sample,
    unpack_sampling_params,
)
from .quant import QuantizedTensor, materialize as _w


#: The parts of a forward, as the device's timeline names them: every
#: operation the model traces lies under ``jax.named_scope("model.<part>")``
#: for one of these (``_scope``), in every program (``prefill`` /
#: ``prefill_packed``, ``decode_step(s)``, ``spec_decode_steps``,
#: ``denoise_step(s)``), because the scopes stand in the helpers and the two
#: layer loops the programs share. A scope is trace-time metadata (the
#: ``op_name`` of the HLO instruction, which the profiler carries as the
#: ``tf_op`` of the device event's metadata): no operation, always on.
#: ``attn`` / ``conv``: a layer's operator from its norm to its output
#: projection and residual (``attn_window``: the same of a sliding layer,
#: whose kernels read the window pool); ``ffn``: ``mlp_norm`` and the dense SwiGLU;
#: ``moe_router``: a routed layer's ``mlp_norm``, ``_moe_gates`` and the
#: sort / permutation of rows by expert; ``moe_experts``: the grouped
#: matmuls, the gate weighting and the un-permutation; ``moe_zero``: the
#: identity (zero-compute) experts' multiply-add; ``kda``: a delta-rule
#: linear-attention mixer whole (projections, convolution, recurrence, gate,
#: output); ``moe_shared``: the
#: shared experts; ``cache_write``: the all-layer scatters into the pools;
#: ``head``: the final norm and the logits; ``sample``:
#: ``ops/sampling.py``'s entry points; ``moe_preroute``: the gates of a layer
#: whose router reads the layer's input, made before its attention
#: (``_preroute``). The Pallas kernels keep their own
#: names inside ``attn`` / ``moe_experts``. What lies under none (the
#: embedding gather, a burst's bookkeeping) a reader calls ``unscoped``.
#: Readers and documents quote this tuple, as they do ``server/engine.py``'s
#: ``STEP_PHASES`` for the host's side.
MODEL_SCOPES = (
    "attn", "attn_window", "conv", "kda", "ffn", "moe_preroute", "moe_router",
    "moe_experts", "moe_zero", "moe_shared", "cache_write", "head", "sample",
)


def _scope(part: str):
    """``jax.named_scope("model.<part>")`` for one of ``MODEL_SCOPES``: a
    context manager, and a decorator for a helper that is one part."""
    return jax.named_scope("model." + part)


def _paged_attention_tp(
    q, kp, vp, block_tables, seq_lens, fresh_k, fresh_v, *, interpret, mesh,
    layer: int = 0, k_scale=None, v_scale=None, scale=None, window: int = 0,
    table_start=None,
):
    """Decode attention, head-parallel over the ``tp`` mesh axis.

    The Pallas kernel is a custom call GSPMD cannot partition, so under a
    mesh it runs inside ``shard_map``: every tp shard holds its slice of
    query/KV heads and computes locally — attention is embarrassingly
    parallel over heads, so no collectives are needed here (the row-parallel
    ``wo`` matmul immediately after carries the cross-shard reduction).

    ``kp``/``vp`` are the FULL multi-layer pools ``[L, P, ps, n_kv, hd]``
    with ``layer`` a word the kernel reads (built inside the mapped function,
    so every layer's call is one kernel a shard) — slicing the layer here
    would force XLA to copy a whole per-layer pool per call (see
    paged_attention's docstring). A shard walks its lanes' pages over its
    own heads as one device does over all. ``fresh_k``/``fresh_v``
    ([b, n_kv, hd]) carry the current token's K/V so pool writes can be
    deferred past attention. ``window`` / ``table_start``: a sliding
    layer's call over the window pools (``ops/paged_attention.py``; single
    shard only).
    """
    if window:
        if mesh is not None or k_scale is not None:
            raise ValueError("a window pool: tp, sp and int8 are not run")
        return paged_attention(
            q, kp, vp, block_tables, seq_lens, fresh_k, fresh_v,
            interpret=interpret, layer=layer, scale=scale, window=window,
            table_start=table_start,
        )
    if mesh is None:
        return paged_attention(
            q, kp, vp, block_tables, seq_lens, fresh_k, fresh_v,
            k_scale=k_scale, v_scale=v_scale,
            interpret=interpret, layer=layer, scale=scale,
        )
    from jax.sharding import PartitionSpec as P

    kv_spec = (
        P(None, None, None, "tp") if kp.ndim == 5 else P(None, None, "tp")
    )
    in_specs = [
        P(None, "tp"), kv_spec, kv_spec, P(), P(),
        P(None, "tp"), P(None, "tp"),
    ]
    args = [q, kp, vp, block_tables, seq_lens, fresh_k, fresh_v]
    if k_scale is not None:
        # Scale pools [L, P, n_kv] shard like the page pools: kv-head axis
        # over tp, so each shard dequantizes its own heads' codes locally.
        scale_spec = (
            P(None, None, "tp") if k_scale.ndim == 3 else P(None, "tp")
        )

        def call(q, kp, vp, bt, sl, fk, fv, ks, vs):
            return paged_attention(
                q, kp, vp, bt, sl, fk, fv, k_scale=ks, v_scale=vs,
                interpret=interpret, layer=layer, scale=scale,
            )

        fn = jax.shard_map(
            call,
            mesh=mesh,
            in_specs=tuple(in_specs + [scale_spec, scale_spec]),
            out_specs=P(None, "tp"),
            check_vma=False,
        )
        return fn(*args, k_scale, v_scale)
    fn = jax.shard_map(
        functools.partial(
            paged_attention, interpret=interpret, layer=layer, scale=scale
        ),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, "tp"),
        check_vma=False,
    )
    return fn(*args)


def _sp_prefill_attention(
    q, k, v, k_pages_l, v_pages_l, block_tables, ctx_lens, positions, valid, mesh
):
    """Sequence-parallel prefill attention: ring over the chunk, exact
    online-softmax merge with the paged prefix context.

    The fresh chunk AND the paged context are both sharded over the
    mesh's ``sp`` axis: shard *r* holds a contiguous chunk slice plus a
    contiguous slice of the context block table, gathers only ITS context
    pages (1/sp of the context HBM reads — replicating the gather per
    shard was the first version's waste), and the ring rotates the
    concatenated [ctx slice ++ chunk slice] K/V payload via ppermute
    (ICI-neighbor traffic only). After sp rotations every query shard has
    attended the full [context ++ chunk] key sequence with one exact
    online-softmax accumulator, so the result matches the single-device
    flash scan up to float associativity. Positions carry visibility:
    context keys ride at position -1 (< any chunk q_pos), chunk keys at
    their absolute positions; right-padded ``valid`` and the per-sequence
    ``ctx_lens`` mask ride the ring as the key-validity lane.

    Removes the single-chip compute/activation ceiling on chunk length —
    the long-context serving path (SURVEY §5: sequence scaling lives in
    the in-tree server; the reference never runs a model).
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import ring_attention_shard

    has_tp = mesh.shape.get("tp", 1) > 1
    sp = mesh.shape["sp"]
    ctx_pages = block_tables.shape[1]
    # Pad the block table so its page axis shards evenly (pad pages carry
    # index 0 but sit beyond every ctx_len, so their keys are masked).
    pad_pages = (-ctx_pages) % sp
    if pad_pages:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad_pages)))

    def body(q, k, v, positions, valid, kp, vp, bt, cl):
        b, s, n_q, d = q.shape
        n_kv = k.shape[2]
        scale = d**-0.5
        pos = positions.astype(jnp.int32)
        page_size = kp.shape[1]
        my = jax.lax.axis_index("sp")
        n_local = bt.shape[1] * page_size  # ctx tokens this shard gathered
        if n_local:
            ctx_k = kp[bt].reshape(b, n_local, n_kv, d)
            ctx_v = vp[bt].reshape(b, n_local, n_kv, d)
            # Global ctx token index of each local slot -> validity.
            ctx_idx = my * n_local + jnp.arange(n_local)
            ctx_valid = ctx_idx[None, :] < cl[:, None]
            ctx_pos = jnp.full((b, n_local), -1, jnp.int32)
            ring_k = jnp.concatenate([ctx_k, k], axis=1)
            ring_v = jnp.concatenate([ctx_v, v], axis=1)
            ring_pos = jnp.concatenate([ctx_pos, pos], axis=1)
            ring_valid = jnp.concatenate([ctx_valid, valid], axis=1)
        else:
            ring_k, ring_v, ring_pos, ring_valid = k, v, pos, valid
        return ring_attention_shard(
            q, ring_k, ring_v, axis_name="sp", scale=scale, q_pos=pos,
            k_pos=ring_pos, k_valid=ring_valid,
        )

    head = "tp" if has_tp else None
    qkv_spec = P(None, "sp", head, None)
    seq_spec = P(None, "sp")
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            qkv_spec, qkv_spec, qkv_spec, seq_spec, seq_spec,
            P(None, None, head, None), P(None, None, head, None),
            P(None, "sp"), P(),
        ),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(
        q, k, v, positions, valid, k_pages_l, v_pages_l, block_tables, ctx_lens
    )


def _check_right_padded_mask(ok) -> None:
    """Host-side assert for prefill's pallas mask contract (opt-in via
    LLMD_CHECK_PREFILL_MASK; see ``prefill`` docstring)."""
    if not bool(ok):
        raise ValueError(
            "prefill(attn_impl='pallas') requires a right-padded prefix "
            "mask: valid[i] == (arange(s) < n_valid[i]); got a mask with "
            "interior holes — use attn_impl='xla' for arbitrary masks"
        )


def _flash_prefill_tp(
    q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid, *,
    layer, interpret, mesh, block_length=0, scale=None, window: int = 0,
    table_start=None,
):
    """Pallas flash prefill, head-parallel over the ``tp`` mesh axis.

    Same shard_map story as `_paged_attention_tp`: the kernel is a custom
    call GSPMD cannot partition, and attention is embarrassingly parallel
    over heads — each shard runs the kernel on its slice of query/KV heads
    and its head-slice of the five-dimensional page pool (``layer`` picks
    the layer inside the kernel: no slice of the pool exists outside it);
    no collectives (the row-parallel ``wo`` right after carries the
    reduction).
    """
    from ..ops.flash_prefill import flash_prefill_paged

    if window:
        # a sliding layer's call over the window pools (single shard only)
        if mesh is not None:
            raise ValueError("a window pool: tp and sp are not run")
        return flash_prefill_paged(
            q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
            interpret=interpret, layer=jnp.int32(layer), scale=scale,
            window=window, table_start=table_start,
        )

    def kernel(q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid, layer):
        return flash_prefill_paged(
            q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
            interpret=interpret, block_length=block_length, layer=layer,
            scale=scale,
        )

    # ``layer`` goes in as an operand: every layer of a program then shares
    # one trace and one lowering of the kernel.
    operands = (
        q, k, v, k_pages, v_pages, block_tables, ctx_lens, n_valid,
        jnp.int32(layer),
    )
    if mesh is None:
        return kernel(*operands)
    from jax.sharding import PartitionSpec as P

    heads = P(None, None, "tp")
    pool = P(None, None, None, "tp")
    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(heads, heads, heads, pool, pool, P(), P(), P(), P()),
        out_specs=heads,
        check_vma=False,
    )
    return fn(*operands)


Params = dict[str, Any]


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: Optional[int] = None  # defaults to hidden_size // n_heads
    rope_theta: float = 500_000.0
    rope_scaling: Optional[RopeScalingConfig] = None
    rms_norm_eps: float = 1e-5
    qkv_bias: bool = False  # Qwen2-style
    qk_norm: bool = False  # Qwen3-style per-head RMSNorm on q/k before RoPE
    tie_word_embeddings: bool = False
    n_experts: int = 0  # sparse-MoE FFN when > 0 (Mixtral/Qwen3-MoE style)
    n_experts_per_tok: int = 2
    # Expert FFN width when decoupled from the dense intermediate size
    # (Qwen3-MoE); None = same as intermediate_size (Mixtral).
    moe_intermediate_size: Optional[int] = None
    # Renormalize the top-k gate weights (Mixtral always; Qwen3-MoE's
    # norm_topk_prob flag).
    norm_topk_prob: bool = True
    # Expert dispatch strategy: "routed" (sort-by-expert + grouped ragged
    # matmuls — per-token expert FLOPs scale with top-k) or "dense" (masked
    # einsum over ALL experts — the numerics oracle, and the layout that
    # GSPMD expert-parallel sharding partitions today).
    moe_dispatch: str = "routed"
    # Grouped-matmul backend for the routed dispatch: "auto" (the Pallas
    # gmm kernel — megablox for bf16, in-VMEM-dequant kernel for int8
    # experts — unless the caller runs with ``interpret=True``, where it
    # is XLA ragged_dot: CPU tests and dry runs), "kernel" (the Pallas
    # path always; interpreted when the caller says so), or "xla"
    # (ragged_dot — the parity oracle). Never chosen from the backend.
    moe_gmm: str = "auto"
    # Gemma-style variations: gated-GELU FFN ("gelu_tanh"), (1+w) RMSNorm
    # scaling (norm_offset=1.0), embeddings scaled by sqrt(hidden_size).
    hidden_act: str = "silu"
    norm_offset: float = 0.0
    scale_embeddings: bool = False
    # Generation by diffusion over blocks (SDAR): 0 = autoregressive. With
    # B > 0 attention is causal between blocks of B absolute positions and
    # full inside one (position i sees j iff j // B <= i // B; B = 1 is the
    # causal model), a block of ``mask_token_id`` rows is denoised against
    # the paged context (``denoise_steps``) and its keys and values are
    # stored by the forward that finds no row masked. The serving engine
    # picks that path from this field alone.
    block_length: int = 0
    mask_token_id: int = 0
    # Latent attention (MLA, DeepSeek-V2/V3 style): ``kv_lora_rank`` > 0
    # replaces per-head keys and values by ONE row a token a layer, the
    # normed latent (``kv_lora_rank`` wide) beside a rotated key shared by
    # every head (``qk_rope_head_dim``). The pool holds that row and
    # nothing else (``kv_row_shape``; no value pool), decode attends in the
    # absorbed form over it (``ops/mla_attention.py``). 0 = the attention
    # above. ``head_dim`` / ``n_kv_heads`` keep what the published config
    # says and size nothing of a latent pool. ``q_lora_rank`` > 0: the
    # query goes through a latent of that width and its norm (``wq_a``,
    # ``q_a_norm``, ``wq_b``; a layer's projection is read from the layer:
    # it has ``wq_a`` or ``wq``). ``mla_scale_q_lora`` / ``mla_scale_kv_lora``
    # (LongCat-Flash): the normed query latent times sqrt(hidden /
    # q_lora_rank), the normed key/value latent times sqrt(hidden /
    # kv_lora_rank); the pool's row holds the scaled latent.
    kv_lora_rank: int = 0
    q_lora_rank: Optional[int] = None
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rotate the pairs (2i, 2i+1) (the published code de-interleaves, then
    # rotates halves: the same scores); False = halves, as everywhere else
    rope_interleave: bool = False
    # Expert layers beyond softmax top-k (DeepSeek-V3 style): shared
    # experts every token takes (one SwiGLU of n_shared_experts x
    # moe_inter), the router's scoring function ("softmax" | "sigmoid":
    # with "sigmoid" a per-expert correction bias chooses the experts and
    # does not weigh them), a factor on the routed sum, group-limited
    # routing (``n_group`` > 1: the experts lie in that many groups, a
    # group's score is the sum of its two largest choice scores, and only
    # the experts of the ``topk_group`` best groups can be chosen:
    # ``_group_limited``), and the number of
    # leading layers whose FFN is the dense one. ``first_k_dense`` decides
    # only which parameters ``init_params`` and the loader make: a layer's
    # FFN is read from the layer itself (it has a ``router`` or not).
    n_shared_experts: int = 0
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    first_k_dense: int = 0
    # What the renormalised sigmoid gates' sum is kept from zero by: the
    # published modelling code's own constant (1e-20 DeepSeek-V3's, 1e-6
    # LFM2's).
    router_norm_eps: float = 1e-20
    # A softmax router with a correction bias that chooses and does not
    # weigh (LongCat-Flash); a sigmoid router always has one. Decides only
    # what ``init_params`` makes: ``_moe_gates`` reads the layer.
    moe_router_bias: bool = False
    # Zero-compute experts (LongCat-Flash): the router scores ``n_experts +
    # n_zero_experts`` outputs, and a chosen id >= ``n_experts`` is an
    # identity expert whose part of the routed sum is ``gate x input``: a
    # multiply-add, no matmul, no row of a grouped one.
    n_zero_experts: int = 0
    # One rank's share of the experts: this process holds the contiguous
    # range ``[expert_first, expert_first + expert_count)`` of the
    # ``n_experts`` the router scores (None: all of them). The router keeps
    # its published width; a place whose expert is held elsewhere adds
    # nothing here, and nothing stands in for the chip that holds it.
    expert_first: int = 0
    expert_count: Optional[int] = None
    # The shortcut-connected double layer (LongCat-Flash): a published
    # layer is two attentions and two dense FFNs in series with ONE routed
    # FFN that reads the first FFN's input and is added after the second
    # (``_ffn``). Decides what ``init_params`` and the loader make and how
    # many layers the pool has (``n_attn_layers``); the bodies read the
    # layer: it has a ``second`` half and a ``moe`` part or it has not.
    double_layer: bool = False
    # Layers whose operator is a gated short convolution instead of
    # attention (LFM2): ``layer_types[i]`` is "conv" or "full_attention"
    # (None: every layer attends). Such a layer keeps ``conv_L_cache - 1``
    # rows of ``hidden_size`` values as its state, whatever the context,
    # and no key or value: the key/value pools' layer axis counts the
    # attention layers alone and a state pool beside them holds, a page, the
    # state after the last token written in it (``init_state_pages``).
    # ``layer_types`` decides only which parameters ``init_params`` and the
    # loader make and how many layers each pool has: a layer's operator is
    # read from the layer itself (it has ``conv_in`` or not). ``conv_bias``
    # is carried for the loader's refusal: a biased convolution is not run.
    layer_types: Optional[tuple] = None
    conv_L_cache: int = 0
    conv_bias: bool = False
    # Window and full attention layers in one model (Trinity / afmoe):
    # ``layer_types[i]`` "sliding_attention" is a layer whose token at ``t``
    # sees the ``sliding_window`` positions ``(t - W, t]`` and no earlier one.
    # Its keys and values live in a WINDOW POOL with page ids of its own
    # (``init_window_pages``: the layer axis counts the sliding layers, the
    # key/value pools' the full ones), which a sequence gives back page by
    # page as it moves on (``server/block_manager.py``). In such a model the
    # sliding layers alone rotate q and k; a full layer takes no positions.
    # ``layer_types`` decides what ``init_params`` and the loader make; the
    # bodies read the layer: a sliding one has the leaf ``window`` (its
    # window as an int32 scalar, read as ``conv_in`` is read: for what it
    # says the layer is, never for its value, which is this static field).
    # 0: no layer has a window, and no program has a window operand.
    sliding_window: int = 0
    # ``sigmoid(x wg)`` on the heads' output before ``wo`` (``x`` the normed
    # input the projections read); a layer that has ``wg`` is gated.
    attn_output_gate: bool = False
    # A norm after the attention and one after the FFN, each before its
    # residual add (``attn_post_norm`` / ``mlp_post_norm``; a layer that has
    # them applies them): ``a = h + N2(Attn(N1 h)); h' = a + N4(F(N3 a))``.
    sandwich_norm: bool = False
    # Delta-rule linear-attention layers whose state is a matrix a head
    # (Kimi Delta Attention as ``bailing_hybrid`` configures it):
    # ``layer_types[i]`` "linear_attention" is a layer that keeps, a sequence,
    # ``n_heads`` matrices ``[kda_head_dim, kda_head_dim]`` float32 and the
    # ``kda_conv_kernel - 1`` newest rows of its q, k, v convolution inputs,
    # and no key or value: its state lives in a STATE POOL OF SLOTS
    # (``init_kda_state``; ``server/block_manager.py`` ``StatePool``), one
    # live slot a sequence and snapshots every ``STATE_SNAPSHOT_TOKENS``. A
    # position never enters. ``kda_safe_gate``: the log decay is
    # ``kda_lower_bound * sigmoid(exp(A_log) * a)``, in (lower bound, 0);
    # else the paper's ``-exp(A_log) * softplus(a)``. ``layer_types`` decides
    # what ``init_params`` and the loader make; the bodies read the layer (it
    # has ``kda_qkv`` or not). ``kda_lora``: the decay's projection is a
    # low-rank pair (``kda_wf_down [d, kda_head_dim]``, ``kda_wf_up``: the
    # rank is the head's size, Kimi Linear's published code) and not the
    # full ``kda_wf``; ``kda_channel_gate``: the output gate is a value a
    # CHANNEL through such a pair (``kda_wg_down``, ``kda_wg_up [rank, H
    # V]``) and not ``kda_wg``'s one a head. Both decide what ``init_params``
    # and the loader make: ``_kda_inputs`` / ``_kda_output`` read the layer's
    # leaves. ``kda_neg_eigval``: ``beta = 2 sigmoid(.)`` in (0, 2), so that
    # ``I - beta k k^T`` has an eigenvalue in (-1, 1) (``allow_neg_eigval``
    # of flash-linear-attention). A non-zero SwiGLU clamp
    # (``expert_swiglu_limits`` / ``shared_swiglu_limits``, a published
    # layer each) is carried for the engine's refusal: it is not run.
    kda_head_dim: int = 0
    kda_conv_kernel: int = 0
    kda_safe_gate: bool = False
    kda_lower_bound: float = -5.0
    kda_lora: bool = False
    kda_channel_gate: bool = False
    kda_neg_eigval: bool = False
    expert_swiglu_limits: Optional[tuple] = None
    shared_swiglu_limits: Optional[tuple] = None
    # The router reads the stream that ENTERS the layer, before any norm and
    # before the attention (SmallThinker): the experts a token takes are
    # chosen from ``h``, its gates carried across the attention, and the
    # experts read ``mlp_norm(h + attention)`` as everywhere. Decides what
    # ``init_params`` and the loader make; the bodies read the layer: a
    # routed one with the leaf ``preroute`` (an int32 scalar, read for what
    # it says the layer is, as ``window`` is) gets its gates from
    # ``_preroute`` ahead of the attention, every other from its own input.
    router_before_attention: bool = False
    # No layer of the model takes a position (NoPE; ``_rotates``).
    no_rope: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.n_heads

    def layer_kind(self, i: int) -> str:
        """"conv", "sliding", "linear" or "attention", as ``layer_types``
        publishes layer ``i``."""
        kind = self.layer_types[i] if self.layer_types else "full_attention"
        return {
            "conv": "conv", "sliding_attention": "sliding",
            "linear_attention": "linear",
        }.get(kind, "attention")

    def _n_layers_of(self, kind: str) -> int:
        return sum(self.layer_kind(i) == kind for i in range(self.n_layers))

    @property
    def n_conv_layers(self) -> int:
        return self._n_layers_of("conv")

    @property
    def n_kda_layers(self) -> int:
        """Layers of the state pool of slots: the linear attentions."""
        return self._n_layers_of("linear")

    @property
    def kda_conv_row(self) -> int:
        """Values of one slot's carried convolution rows in one linear
        layer: the ``kda_conv_kernel - 1`` newest rows of ``[q | k | v]``
        before the convolution, oldest first, side by side
        (``kda_conv_tile``: how the state pool holds them)."""
        return max(self.kda_conv_kernel - 1, 0) * 3 * self.n_heads * self.kda_head_dim

    @property
    def kda_conv_tile(self) -> tuple[int, int]:
        """The two minor axes a slot's ``kda_conv_row`` values take in the
        state pool, so that a slot is whole tiles and one contiguous piece
        of HBM: ``[row // lanes, lanes]`` with ``lanes`` the largest power of
        two up to 128 that divides the row. At the published widths ``[288,
        128]``, 18 whole bf16 tiles of ``(16, 128)`` and no padding, and the
        TPU compiler makes ONE scatter fusion of a decode step's write of
        384 slots. With the slots second-minor (``[.., slots, row]``) a slot
        is one sublane of ``row / 128`` tiles it shares with fifteen other
        slots, and the compiler writes a step's slots in a loop of
        single-row updates that each rewrite all sixteen
        (``tests/test_pool_layout.py``, ``TestTheStatePoolOfSlots``; the
        times: PERF.md section 6, PR 48); a ``[3, 12288]`` minor shape would
        pad three sublanes to sixteen."""
        lanes = math.gcd(self.kda_conv_row, 128)
        return self.kda_conv_row // lanes, lanes

    @property
    def kda_state_bytes(self) -> int:
        """Bytes of one slot of the state pool, every linear layer."""
        return self.n_kda_layers * (
            self.n_heads * self.kda_head_dim**2 * 4
            + self.kda_conv_row * jnp.dtype(self.dtype).itemsize
        )

    @property
    def layer_group_size(self) -> Optional[int]:
        """``layer_group_size`` as ``bailing_hybrid`` publishes it: every
        layer of a group is a linear attention but its last."""
        if not self.layer_types or "linear_attention" not in self.layer_types:
            return None
        return 1 + next(
            i for i, kind in enumerate(self.layer_types)
            if kind != "linear_attention"
        )

    @property
    def context_pool_name(self) -> str:
        """What a refusal calls the pool of pages a state pool of slots lies
        beside: the layers between the linear ones are latent attentions or
        attend over per-head keys and values."""
        return "latent pool" if self.kv_lora_rank else "key/value pools"

    @property
    def gqa_layers(self) -> Optional[list]:
        """The layers that attend over their whole context, as ``solar_open2``
        publishes them (whole, whatever ``n_layers`` is run of them)."""
        if self.layer_types is None:
            return None
        return [i for i, kind in enumerate(self.layer_types)
                if kind == "full_attention"]

    @property
    def use_rope(self) -> bool:
        """``use_rope`` of a published file: some layer rotates q and k."""
        return not self.no_rope

    @property
    def kda_full_proj(self) -> bool:
        """``kda_use_full_proj`` of a published file: no low-rank pair."""
        return not self.kda_lora

    @property
    def expert_swiglu_limit_list(self) -> Optional[list]:
        """The routed experts' SwiGLU clamps as a published file's JSON gives
        them (a list a published layer; a tuple here, so a preset hashes)."""
        limits = self.expert_swiglu_limits
        return None if limits is None else list(limits)

    @property
    def share_expert_swiglu_limit_list(self) -> Optional[list]:
        limits = self.shared_swiglu_limits
        return None if limits is None else list(limits)

    @property
    def n_window_layers(self) -> int:
        """Layers of the window pools: the sliding attentions."""
        return self._n_layers_of("sliding")

    @property
    def n_attn_layers(self) -> int:
        """Layers of the key/value pools: the attentions that see their
        whole context (two a published layer of a ``double_layer`` model)."""
        return self._n_layers_of("attention") * (1 + self.double_layer)

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this process holds."""
        return self.n_experts if self.expert_count is None else self.expert_count

    @property
    def router_outputs(self) -> int:
        """Columns of the router: the routed experts, then the zero ones."""
        return self.n_experts + self.n_zero_experts

    @property
    def holds_every_expert(self) -> bool:
        """No zero expert and no expert held elsewhere: the routed layer is
        the program it always was."""
        return not self.n_zero_experts and self.experts_held == self.n_experts

    @property
    def zero_expert_type(self) -> Optional[str]:
        """What a zero-compute expert computes, as a published file says it
        (only the identity is run)."""
        return "identity" if self.n_zero_experts else None

    @property
    def layer_types_published(self) -> Optional[list]:
        """``layer_types`` as a published file's JSON gives it: a list (a
        tuple here, so that a preset hashes), whole, whatever ``n_layers``
        is run of it."""
        return None if self.layer_types is None else list(self.layer_types)

    @property
    def sliding_window_layout(self) -> Optional[list]:
        """``layer_types`` as ``smallthinker`` publishes it: a 1 for a layer
        that sees a window, a 0 for one that sees its whole context (whole,
        whatever ``n_layers`` is run of it)."""
        if self.layer_types is None:
            return None
        return [int(kind == "sliding_attention") for kind in self.layer_types]

    @property
    def rope_layout(self) -> Optional[list]:
        """Which layers rotate q and k, as ``smallthinker`` publishes it: in
        a model with sliding layers those and no other (``_rotates``)."""
        if not self.sliding_window or self.layer_types is None:
            return None
        return self.sliding_window_layout

    @property
    def router_applies_softmax(self) -> bool:
        return self.n_experts > 0 and self.moe_scoring == "softmax"

    @property
    def use_expert_bias(self) -> bool:
        """A sigmoid router's experts are chosen by score + bias."""
        return self.n_experts > 0 and self.moe_scoring == "sigmoid"

    @property
    def kv_heads_per_row(self) -> int:
        """KV heads sharing one 128-lane row of a key/value pool. The TPU
        compiler pads a minor dimension under 128 up to 128 lanes in HBM
        whatever the array says (twice the bytes at head size 64), so where
        heads are narrower than a row the pool's row says what is stored:
        ``128 // hd`` neighbouring heads side by side (``kv_row_shape``).
        Taken only by a model with convolution layers, for which ``tp`` > 1,
        the int8 pool and the page movers are refused: those paths read a
        row as one head (PERF.md, Open questions)."""
        g = 128 // self.hd if self.hd < 128 and 128 % self.hd == 0 else 1
        return g if self.layer_types and self.n_kv_heads % g == 0 else 1

    @property
    def state_row(self) -> int:
        """Values of one page's state slot in one convolution layer."""
        return max(self.conv_L_cache - 1, 0) * self.hidden_size

    @property
    def latent_width(self) -> int:
        """Values of one token's latent row (0: per-head keys and values)."""
        return self.kv_lora_rank + self.qk_rope_head_dim if self.kv_lora_rank else 0

    @property
    def kv_row_shape(self) -> tuple:
        """Minor dimensions of one token's row in a page pool: what every
        pool, page and wire size follows (never ``n_kv_heads x hd`` alone).
        A latent row is held in whole tiles of 128 lanes, zeros past its
        values (576 values in 640): the TPU compiler lays a minor dimension
        of 576 out as 640 whatever the array says (``bf16[8,16384,16,576]``
        is ``memref<8x16384x16x640>`` in HBM), and Mosaic cuts no page tile
        out of a padded one ("slice shape must be aligned to tiling (128)").
        So the padding costs no byte that was not already there, and the
        array's own ``nbytes`` is what the device holds."""
        if self.kv_lora_rank:
            return (-(-self.latent_width // 128) * 128,)
        g = self.kv_heads_per_row
        return (self.n_kv_heads // g, g * self.hd)

    @property
    def moe_inter(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def act_fn(self):
        if self.hidden_act == "silu":
            return jax.nn.silu
        if self.hidden_act in ("gelu_tanh", "gelu_pytorch_tanh"):
            return functools.partial(jax.nn.gelu, approximate=True)
        if self.hidden_act == "gelu":
            return functools.partial(jax.nn.gelu, approximate=False)
        if self.hidden_act == "relu":  # a ReGLU expert: relu(gate) * up
            return jax.nn.relu
        raise ValueError(f"unsupported hidden_act {self.hidden_act!r}")


#: Flagship config (meta-llama/Llama-3.1-8B, incl. its llama3 rope scaling).
LLAMA_3_8B = LlamaConfig(rope_scaling=RopeScalingConfig())

LLAMA_3_70B = LlamaConfig(
    hidden_size=8192,
    intermediate_size=28_672,
    n_layers=80,
    n_heads=64,
    n_kv_heads=8,
    rope_scaling=RopeScalingConfig(),
)

#: Qwen2.5-0.5B-Instruct (the reference's chat-templating benchmark model,
#: `pkg/preprocessing/chat_completions/README.md:118`): QKV biases, tied
#: embeddings.
QWEN2_5_0_5B = LlamaConfig(
    vocab_size=151_936,
    hidden_size=896,
    intermediate_size=4_864,
    n_layers=24,
    n_heads=14,
    n_kv_heads=2,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    qkv_bias=True,
    tie_word_embeddings=True,
)

#: Qwen3-32B (the reference's 73-capacity benchmark model,
#: `benchmarking/73-capacity/README.md:9`): per-head qk-norm, decoupled
#: head_dim, no biases.
QWEN3_32B = LlamaConfig(
    vocab_size=151_936,
    hidden_size=5_120,
    intermediate_size=25_600,
    n_layers=64,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    qk_norm=True,
)

#: Mixtral-8x7B-v0.1 (`BASELINE.json` configs[4]: multi-host MoE serving):
#: Llama-shaped attention (GQA 32/8) with 8 top-2-routed SwiGLU experts.
MIXTRAL_8X7B = LlamaConfig(
    vocab_size=32_000,
    hidden_size=4_096,
    intermediate_size=14_336,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    rope_theta=1_000_000.0,
    n_experts=8,
    n_experts_per_tok=2,
)

#: google/gemma-7b: MHA (16/16) with decoupled head_dim 256, gated-GELU FFN,
#: (1+w) RMSNorm, sqrt(d)-scaled tied embeddings.
GEMMA_7B = LlamaConfig(
    vocab_size=256_000,
    hidden_size=3_072,
    intermediate_size=24_576,
    n_layers=28,
    n_heads=16,
    n_kv_heads=16,
    head_dim=256,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    tie_word_embeddings=True,
    hidden_act="gelu_tanh",
    norm_offset=1.0,
    scale_embeddings=True,
)

#: Tiny config for tests / CPU dry-runs.
TINY_LLAMA = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    rope_theta=10_000.0,
    dtype=jnp.float32,
)

#: Tiny Gemma-shaped config for tests / CPU dry-runs.
TINY_GEMMA = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    head_dim=24,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    tie_word_embeddings=True,
    hidden_act="gelu_tanh",
    norm_offset=1.0,
    scale_embeddings=True,
    dtype=jnp.float32,
)

#: Qwen3-30B-A3B (128-expert top-8 MoE with qk-norm, decoupled 768-wide
#: experts, renormalized gates per its checkpoint config).
#:
#: Dispatch: ``moe_dispatch="routed"`` (the default) — sort-by-expert +
#: grouped ragged matmuls, so per-token expert FLOPs scale with top-k
#: (~E/k below the masked-dense oracle at E=128/top-8). Under an
#: expert-parallel mesh the routed path runs inside shard_map over the
#: expert axis (see ``parallel/sharding.py``); single-device it uses the
#: global ``ragged_dot`` pipeline.
QWEN3_30B_A3B = LlamaConfig(
    vocab_size=151_936,
    hidden_size=2_048,
    intermediate_size=6_144,
    n_layers=48,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    qk_norm=True,
    n_experts=128,
    n_experts_per_tok=8,
    moe_intermediate_size=768,
    norm_topk_prob=True,
)

#: Tiny Qwen3-MoE-shaped config (qk-norm + MoE) for tests / CPU dry-runs.
TINY_QWEN3_MOE = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=24,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    qk_norm=True,
    n_experts=4,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    dtype=jnp.float32,
)

#: JetLM/SDAR-30B-A3B-Chat (``model_type: sdar_moe``): Qwen3-30B-A3B's
#: decoder, every width the same, generating by diffusion over blocks. The
#: published config gives neither block length nor schedule; 4 and mask id
#: 151669 are the family's released ``generate.py``'s.
SDAR_30B_A3B = dataclasses.replace(
    QWEN3_30B_A3B, block_length=4, mask_token_id=151_669
)

#: Tiny SDAR-MoE-shaped config (block diffusion over qk-norm + MoE) for
#: tests / CPU dry-runs; the mask id is the vocabulary's last.
TINY_SDAR_MOE = dataclasses.replace(
    TINY_QWEN3_MOE, block_length=4, mask_token_id=255
)

#: kakaocorp/kanana-2-30b-a3b-instruct-2601 (``model_type: deepseek_v3``):
#: latent attention without a low-rank query path, 128 routed experts top-6
#: with sigmoid scores and a correction bias, two shared experts, one
#: leading dense layer. ``head_dim`` 64 and 32 KV heads are the published
#: file's (its ``head_dim`` is the rope part); the pool is 576 wide.
KANANA_2_30B_A3B = LlamaConfig(
    vocab_size=128_256,
    hidden_size=2_048,
    intermediate_size=6_144,
    n_layers=48,
    n_heads=32,
    n_kv_heads=32,
    head_dim=64,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-6,
    n_experts=128,
    n_experts_per_tok=6,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_interleave=True,
    n_shared_experts=2,
    moe_scoring="sigmoid",
    routed_scaling_factor=2.448,
    first_k_dense=1,
)

#: Tiny latent-attention MoE (a dense layer, 3 expert layers, 8 experts
#: top-2, a shared expert, latent 32 + rope 8) for tests / CPU dry-runs.
TINY_MLA_MOE = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=4,
    n_heads=4,
    n_kv_heads=4,
    head_dim=8,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    n_experts=8,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_interleave=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=2.448,
    first_k_dense=1,
    dtype=jnp.float32,
)

#: meituan-longcat/LongCat-Flash-Omni's language model (``LongCat-Flash``'s
#: decoder; the towers and the codec decoder are outside): 28 double layers
#: (two latent attentions with a low-rank query path and the two
#: ``mla_scale_*`` factors, two dense FFNs, one routed FFN beside the second
#: attention and FFN), a softmax router over 512 routed + 256 identity
#: experts with a correction bias that chooses, top-12, gates times 6 and
#: not renormalised. ``head_dim`` / ``n_kv_heads`` restate the rope part and
#: the head count (as ``KANANA_2_30B_A3B``'s): neither sizes the pool. No
#: chip holds a layer's 512 experts: a configuration states its share
#: (``expert_first`` / ``expert_count``) and its cut of depth and vocabulary.
LONGCAT_FLASH_OMNI = LlamaConfig(
    vocab_size=131_072,
    hidden_size=6_144,
    intermediate_size=12_288,
    n_layers=28,
    n_heads=64,
    n_kv_heads=64,
    head_dim=64,
    rope_theta=10_000_000.0,
    rms_norm_eps=1e-5,
    n_experts=512,
    n_experts_per_tok=12,
    moe_intermediate_size=2_048,
    norm_topk_prob=False,
    kv_lora_rank=512,
    q_lora_rank=1_536,
    mla_scale_q_lora=True,
    mla_scale_kv_lora=True,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_interleave=True,
    routed_scaling_factor=6.0,
    moe_router_bias=True,
    n_zero_experts=256,
    double_layer=True,
)

#: Tiny shortcut-connected MoE (two double layers; 16 routed + 8 identity
#: router outputs, top-4, 4 of the 16 held; latent 32 + rope 8 behind a
#: query latent of 24) for tests / CPU dry-runs.
TINY_SCMOE = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    head_dim=8,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    n_experts=16,
    n_experts_per_tok=4,
    moe_intermediate_size=48,
    norm_topk_prob=False,
    kv_lora_rank=32,
    q_lora_rank=24,
    mla_scale_q_lora=True,
    mla_scale_kv_lora=True,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_interleave=True,
    routed_scaling_factor=6.0,
    moe_router_bias=True,
    n_zero_experts=8,
    expert_first=4,
    expert_count=4,
    double_layer=True,
    dtype=jnp.float32,
)

_CONV, _ATTN = "conv", "full_attention"

#: LiquidAI/LFM2-8B-A1B (``model_type: lfm2_moe``): 18 gated short
#: convolutions (three taps) and 6 GQA layers of head size 64, two leading
#: dense layers, then 32 experts top-4 with sigmoid scores and an expert
#: bias that chooses and does not weigh. The published file says
#: ``norm_eps`` and gives no head size (2048 / 32); the tied head, the
#: scoring function and the 1e-6 are the family's modelling code's.
LFM2_8B_A1B = LlamaConfig(
    vocab_size=65_536,
    hidden_size=2_048,
    intermediate_size=7_168,
    n_layers=24,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    rope_theta=1_000_000.0,
    rms_norm_eps=1e-5,
    qk_norm=True,
    tie_word_embeddings=True,
    n_experts=32,
    n_experts_per_tok=4,
    moe_intermediate_size=1_792,
    norm_topk_prob=True,
    moe_scoring="sigmoid",
    routed_scaling_factor=1.0,
    router_norm_eps=1e-6,
    first_k_dense=2,
    layer_types=(
        _CONV, _CONV, _ATTN, _CONV, _CONV, _CONV, _ATTN, _CONV, _CONV, _CONV,
        _ATTN, _CONV, _CONV, _CONV, _ATTN, _CONV, _CONV, _CONV, _ATTN, _CONV,
        _CONV, _ATTN, _CONV, _CONV,
    ),
    conv_L_cache=3,
)

#: Tiny LFM2-MoE-shaped config (two dense layers, then a period and a half:
#: conv, conv, attention, conv x3, attention, conv; 8 experts top-2; head
#: size 64, two KV heads a pool row) for tests / CPU dry-runs.
TINY_LFM2_MOE = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=8,
    n_heads=8,
    n_kv_heads=4,
    head_dim=64,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    qk_norm=True,
    tie_word_embeddings=True,
    n_experts=8,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    moe_scoring="sigmoid",
    routed_scaling_factor=1.0,
    router_norm_eps=1e-6,
    first_k_dense=2,
    layer_types=(_CONV, _CONV, _ATTN, _CONV, _CONV, _CONV, _ATTN, _CONV),
    conv_L_cache=3,
    dtype=jnp.float32,
)

_SWA = "sliding_attention"

#: arcee-ai/Trinity-Large-Preview (``model_type: afmoe``): 45 sliding layers
#: of window 4096 and 15 full ones, every fourth; GQA 48 / 8 with per-head
#: q/k norm and a sigmoid gate on the heads' output; rope on the sliding
#: layers only; four norms a layer; the embedding times sqrt(hidden); six
#: leading dense layers, then 256 sigmoid-routed experts top-4 (a bias that
#: chooses, gates renormalised, x 2.448) beside one shared expert. No chip
#: holds a layer's 256 experts: a configuration states its share
#: (``expert_first`` / ``expert_count``) and its cut of depth and vocabulary.
TRINITY_LARGE_PREVIEW = LlamaConfig(
    vocab_size=200_192,
    hidden_size=3_072,
    intermediate_size=12_288,
    n_layers=60,
    n_heads=48,
    n_kv_heads=8,
    head_dim=128,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    qk_norm=True,
    scale_embeddings=True,
    n_experts=256,
    n_experts_per_tok=4,
    moe_intermediate_size=3_072,
    norm_topk_prob=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=2.448,
    first_k_dense=6,
    layer_types=(_SWA, _SWA, _SWA, _ATTN) * 15,
    sliding_window=4_096,
    attn_output_gate=True,
    sandwich_norm=True,
)

#: Tiny window-and-full MoE (sliding, sliding, sliding, full, sliding; window
#: 8; a dense layer, then 8 experts top-2 of which 4 are held, one shared)
#: for tests / CPU dry-runs.
TINY_SWA_MOE = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=5,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    qk_norm=True,
    scale_embeddings=True,
    n_experts=8,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=2.448,
    first_k_dense=1,
    expert_first=2,
    expert_count=4,
    layer_types=(_SWA, _SWA, _SWA, _ATTN, _SWA),
    sliding_window=8,
    attn_output_gate=True,
    sandwich_norm=True,
    dtype=jnp.float32,
)

#: PowerInfer/SmallThinker-21BA3B-Instruct (``model_type: smallthinker``): 52
#: layers in periods of four, a FULL layer that rotates nothing FIRST, then
#: three sliding ones of window 4096 that rotate (``sliding_window_layout``
#: and ``rope_layout`` ``[0, 1, 1, 1]`` x 13); GQA 28 / 4 heads of 128 (a
#: group of 7; 28 x 128 is not the hidden size), no q/k norm, no bias; every
#: layer 64 ReGLU experts of width 768 (``relu(gate) * up``), top-6 of the
#: logits with a softmax over the six, no shared expert, no dense layer, and
#: a router that reads the layer's INPUT, before the first norm and the
#: attention (``router_before_attention``). ``intermediate_size`` restates
#: the experts' width: the published file has no dense FFN and no key for one.
SMALLTHINKER_21B_A3B = LlamaConfig(
    vocab_size=151_936,
    hidden_size=2_560,
    intermediate_size=768,
    n_layers=52,
    n_heads=28,
    n_kv_heads=4,
    head_dim=128,
    rope_theta=1_500_000.0,
    rms_norm_eps=1e-6,
    n_experts=64,
    n_experts_per_tok=6,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    hidden_act="relu",
    layer_types=(_ATTN, _SWA, _SWA, _SWA) * 13,
    sliding_window=4_096,
    router_before_attention=True,
)

#: Tiny SmallThinker (full, sliding, sliding, sliding; window 8; 7 query
#: heads on 1 KV head; 8 ReGLU experts top-2, the router before the
#: attention) for tests / CPU dry-runs.
TINY_SMALLTHINKER = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=48,
    n_layers=4,
    n_heads=7,
    n_kv_heads=1,
    head_dim=16,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    n_experts=8,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    hidden_act="relu",
    layer_types=(_ATTN, _SWA, _SWA, _SWA),
    sliding_window=8,
    router_before_attention=True,
    dtype=jnp.float32,
)

_KDA = "linear_attention"

#: inclusionAI/Ling-3.0-flash (``model_type: bailing_hybrid``): 42 layers in
#: groups of six, five delta-rule linear attentions (32 heads, a state of
#: 128 x 128 a head, a four-tap convolution on q, k and v, the safe gate)
#: then one latent attention of ``KANANA_2_30B_A3B``'s shape; two leading
#: dense layers, then 512 sigmoid-routed experts of width 768, top-8 over
#: the 4 best of 8 groups, one shared expert, gates renormalised, x 2.5.
#: ``head_dim`` 128 is the published file's (a linear head's size; the
#: latent layers' rope part is ``qk_rope_head_dim``), 32 KV heads its head
#: count: neither sizes the latent pool. No chip holds a layer's 512 experts: a
#: configuration states its share (``expert_first`` / ``expert_count``: one
#: routing group) and its cut of depth and vocabulary. The SwiGLU clamp of
#: the last eight layers is carried and refused where a run layer has one.
LING_3_FLASH = LlamaConfig(
    vocab_size=157_184,
    hidden_size=2_560,
    intermediate_size=6_144,
    n_layers=42,
    n_heads=32,
    n_kv_heads=32,
    head_dim=128,
    rope_theta=6_000_000.0,
    rms_norm_eps=1e-6,
    n_experts=512,
    n_experts_per_tok=8,
    moe_intermediate_size=768,
    norm_topk_prob=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_interleave=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=2.5,
    n_group=8,
    topk_group=4,
    first_k_dense=2,
    layer_types=((_KDA,) * 5 + (_ATTN,)) * 7,
    kda_head_dim=128,
    kda_conv_kernel=4,
    kda_safe_gate=True,
    kda_lower_bound=-5.0,
    expert_swiglu_limits=(0,) * 35 + (4,) * 7,
    shared_swiglu_limits=(0,) * 34 + (5,) * 6 + (7,) * 2,
)

#: Tiny hybrid of linear and latent attention (two groups of 2 linear + 1
#: latent; a leading dense layer, then 8 experts in 4 groups, top-2 over the
#: 2 best groups, one shared; 4 heads with a 16 x 16 state, four taps) for
#: tests / CPU dry-runs.
TINY_LING_HYBRID = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=6,
    n_heads=4,
    n_kv_heads=4,
    head_dim=16,
    rope_theta=10_000.0,
    rms_norm_eps=1e-6,
    n_experts=8,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_interleave=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=2.5,
    n_group=4,
    topk_group=2,
    first_k_dense=1,
    layer_types=(_KDA, _KDA, _ATTN) * 2,
    kda_head_dim=16,
    kda_conv_kernel=4,
    kda_safe_gate=True,
    kda_lower_bound=-5.0,
    dtype=jnp.float32,
)

#: upstage/Solar-Open2-250B (``model_type: solar_open2``): 48 layers in
#: periods of four, a softmax GQA layer FIRST (``gqa_layers`` 0, 4, .. 44: 64
#: query heads on 8 KV heads of 128, a sigmoid gate on the heads' output, no
#: q/k norm) then three delta-rule linear attentions (64 heads, a state of
#: 128 x 128 a head, a four-tap convolution, the paper's gate through a
#: low-rank pair, a channel-wise output gate through another, ``beta`` in (0,
#: 2)); NO layer takes a position (``no_rope``); every layer 320
#: sigmoid-routed experts of width 1280, top-8 in one group, gates
#: renormalised, x 1, beside one shared expert; no dense layer
#: (``intermediate_size`` sizes nothing). No chip holds a layer's 320
#: experts: a configuration states its share (``expert_first`` /
#: ``expert_count``) and its cut of depth and vocabulary.
SOLAR_OPEN2_250B = LlamaConfig(
    vocab_size=196_608,
    hidden_size=4_096,
    intermediate_size=10_240,
    n_layers=48,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    n_experts=320,
    n_experts_per_tok=8,
    moe_intermediate_size=1_280,
    norm_topk_prob=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=1.0,
    layer_types=(_ATTN, _KDA, _KDA, _KDA) * 12,
    kda_head_dim=128,
    kda_conv_kernel=4,
    kda_lora=True,
    kda_channel_gate=True,
    kda_neg_eigval=True,
    attn_output_gate=True,
    no_rope=True,
)

#: Tiny hybrid of GQA and linear attention (two periods of one gated GQA
#: layer, 4 query heads of 16 on 1 KV head, and three linear ones, 4 heads
#: with a 16 x 16 state and low-rank pairs of rank 16; no positions; 8
#: experts top-2 and one shared in every layer) for tests / CPU dry-runs.
TINY_SOLAR_HYBRID = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    n_layers=8,
    n_heads=4,
    n_kv_heads=1,
    head_dim=16,
    rope_theta=10_000.0,
    rms_norm_eps=1e-5,
    n_experts=8,
    n_experts_per_tok=2,
    moe_intermediate_size=48,
    norm_topk_prob=True,
    n_shared_experts=1,
    moe_scoring="sigmoid",
    routed_scaling_factor=1.0,
    layer_types=(_ATTN, _KDA, _KDA, _KDA) * 2,
    kda_head_dim=16,
    kda_conv_kernel=4,
    kda_lora=True,
    kda_channel_gate=True,
    kda_neg_eigval=True,
    attn_output_gate=True,
    no_rope=True,
    dtype=jnp.float32,
)

#: Tiny MoE config (Mixtral-shaped) for tests / CPU dry-runs.
TINY_MOE = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=96,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    rope_theta=10_000.0,
    n_experts=4,
    n_experts_per_tok=2,
    dtype=jnp.float32,
)


def init_params(
    rng: jax.Array,
    cfg: LlamaConfig,
    quantize: Optional[str] = None,
    quantize_experts: bool = False,
) -> Params:
    """Random-init parameter pytree (serving loads real checkpoints via
    ``load_hf_state_dict``; training uses this directly).

    ``quantize="int8"`` quantizes each matmul weight the moment it is
    created, so the full-precision tree is never resident — required to
    init 8B-class models on a single chip (16 GB bf16 + 8 GB int8 would
    not fit; see models/quant.py). MoE expert stacks stay in model dtype
    unless ``quantize_experts=True`` (opt-in; the gmm kernel dequantizes
    int8 experts in VMEM while halving expert HBM).
    """
    if quantize not in (None, "int8"):
        raise ValueError(f"unknown quantize mode {quantize!r}")
    d, hd = cfg.hidden_size, cfg.hd
    n_q, n_kv, inter = cfg.n_heads, cfg.n_kv_heads, cfg.intermediate_size

    def dense(key, shape, scale_dim, quantizable=True):
        w = (jax.random.normal(key, shape, jnp.float32) * (scale_dim**-0.5)).astype(
            cfg.dtype
        )
        if quantize and quantizable:
            from .quant import quantize_tensor

            return quantize_tensor(w)
        return w

    # Gemma's (1+w) convention stores w≈0 for an identity norm.
    def norm_init(shape):
        return (jnp.zeros if cfg.norm_offset else jnp.ones)(shape, cfg.dtype)

    def latent_attention(k) -> Params:
        """One latent attention with its norm (``k``: eight keys, the first
        four used); the query's projection is the low-rank pair where the
        model has ``q_lora_rank``."""
        dc, dn, dr, dv = (
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim,
        )
        part = {"attn_norm": norm_init((d,))}
        if cfg.q_lora_rank:
            dq = cfg.q_lora_rank
            ka, kb = jax.random.split(k[0])
            part["wq_a"] = dense(ka, (d, dq), d)
            part["q_a_norm"] = norm_init((dq,))
            part["wq_b"] = dense(kb, (dq, n_q * (dn + dr)), dq)
        else:
            part["wq"] = dense(k[0], (d, n_q * (dn + dr)), d)
        part.update(
            wkv_a=dense(k[1], (d, dc + dr), d),
            kv_norm=norm_init((dc,)),
            wkv_b=dense(k[2], (dc, n_q * (dn + dv)), dc),
            wo=dense(k[3], (n_q * dv, d), n_q * dv),
            mlp_norm=norm_init((d,)),
        )
        return part

    def dense_ffn(k) -> Params:
        return {
            "w_gate": dense(k[4], (d, inter), d),
            "w_up": dense(k[5], (d, inter), d),
            "w_down": dense(k[6], (inter, d), inter),
        }

    def routed_ffn(k, layer_key) -> Params:
        """A routed FFN's parameters: the router over every output it
        scores, the stacks of the experts this process holds."""
        e, f = cfg.experts_held, cfg.moe_inter
        # Router stays full precision: tiny, and routing decisions are
        # the most quantization-sensitive computation in an MoE.
        part = {
            "router": dense(
                k[7], (d, cfg.router_outputs), d, quantizable=False
            ),
            "w_gate": dense(k[4], (e, d, f), d, quantizable=quantize_experts),
            "w_up": dense(k[5], (e, d, f), d, quantizable=quantize_experts),
            "w_down": dense(k[6], (e, f, d), f, quantizable=quantize_experts),
        }
        # (keys folded in, not split off: the trees of the models
        # without these parts stay bit for bit what they were)
        extra = jax.random.split(jax.random.fold_in(layer_key, 1), 4)
        if cfg.moe_scoring == "sigmoid" or cfg.moe_router_bias:
            # The correction bias chooses the experts and does not weigh
            # them; float32, not trained by gradient. Drawn here (a
            # checkpoint's is near zero and balances the load), so that a
            # program that weighs with it, or chooses without it, is not
            # this model: half a sigmoid router's spread of scores (0.21);
            # a fifth of a softmax router's over n outputs (sqrt(e - 1) / n:
            # at the whole spread a drawn bias moved the load of a rank's 16
            # experts by a half from seed to seed, PERF.md section 6, PR 41),
            # and a fifth of a sigmoid router's too where a rank holds a share
            # of the experts, for that reason; a twentieth where that share
            # is a routing GROUP: a group's score is the sum of its two
            # LARGEST biased scores, so a drawn bias moves a whole group's
            # load (the held group's places by a fifth from seed to seed at
            # 0.04, a twentieth at 0.01: PERF.md section 6, PR 47).
            if cfg.moe_scoring != "sigmoid":
                spread = 0.25 / cfg.router_outputs
            elif cfg.holds_every_expert:
                spread = 0.1
            else:
                spread = 0.01 if cfg.n_group > 1 else 0.04
            part["router_bias"] = spread * jax.random.normal(
                extra[0], (cfg.router_outputs,), jnp.float32
            )
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            part["ws_gate"] = dense(extra[1], (d, fs), d)
            part["ws_up"] = dense(extra[2], (d, fs), d)
            part["ws_down"] = dense(extra[3], (fs, d), fs)
        return part

    def low_rank(key, name) -> Params:
        """A linear layer's pair ``[d, rank] [rank, H K]`` with the head's
        size as rank, each drawn at the scale of its own input (the
        product's entries spread as a full projection's)."""
        rank = cfg.kda_head_dim
        down, up = jax.random.split(key)
        return {
            name + "_down": dense(down, (d, rank), d, quantizable=False),
            name + "_up": dense(
                up, (rank, n_q * rank), rank, quantizable=False),
        }

    keys = jax.random.split(rng, cfg.n_layers + 2)
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 8)
        if cfg.double_layer:
            # One published layer: the first attention and dense FFN at the
            # top, the routed FFN that reads the first FFN's input under
            # ``moe`` (its stacks keep the names every routed layer has),
            # the second attention and dense FFN under ``second``.
            if not cfg.kv_lora_rank or not cfg.n_experts:
                raise ValueError(
                    "double_layer: latent attention and routed experts "
                    "are what is run"
                )
            k2 = jax.random.split(jax.random.fold_in(keys[i], 2), 8)
            k3 = jax.random.split(jax.random.fold_in(keys[i], 3), 8)
            layers.append({
                **latent_attention(k), **dense_ffn(k),
                "moe": routed_ffn(k3, keys[i]),
                "second": {**latent_attention(k2), **dense_ffn(k2)},
            })
            continue
        if cfg.layer_kind(i) == "conv":
            # ``[B | C | x] = u conv_in``; one ``conv_L_cache``-tap filter a
            # channel (row j weighs ``z`` of ``conv_L_cache - 1 - j`` tokens
            # back; kept full precision: it is tiny); ``conv_out`` after the
            # gate.
            layer = {
                "attn_norm": norm_init((d,)),
                "conv_in": dense(k[0], (d, 3 * d), d),
                "conv_w": dense(
                    k[1], (cfg.conv_L_cache, d), cfg.conv_L_cache,
                    quantizable=False,
                ),
                "conv_out": dense(k[3], (d, d), d),
                "mlp_norm": norm_init((d,)),
            }
        elif cfg.layer_kind(i) == "linear":
            # ``[q | k | v] = x kda_qkv`` (one product), a ``kda_conv_kernel``
            # -tap filter a channel over the three (row j weighs the input
            # of ``kda_conv_kernel - 1 - j`` tokens back; full precision),
            # the gate's projection with its bias and a head's ``A_log``,
            # one ``beta`` and one output gate a head, the heads' norm.
            # (keys folded in: every other tree stays what it was)
            hk = n_q * cfg.kda_head_dim
            kk = jax.random.split(jax.random.fold_in(keys[i], 5), 4)
            layer = {
                "attn_norm": norm_init((d,)),
                "kda_qkv": dense(k[0], (d, 3 * hk), d),
                "kda_conv_w": dense(
                    k[1], (cfg.kda_conv_kernel, 3 * hk), cfg.kda_conv_kernel,
                    quantizable=False,
                ),
                **(low_rank(k[2], "kda_wf") if cfg.kda_lora
                   else {"kda_wf": dense(k[2], (d, hk), d)}),
                "kda_dt_bias": 0.5 * jax.random.normal(kk[0], (hk,), jnp.float32),
                "kda_A_log": jnp.log(
                    jax.random.uniform(kk[1], (n_q,), jnp.float32, 0.5, 4.0)
                ),
                "kda_wb": dense(kk[2], (d, n_q), d, quantizable=False),
                **(low_rank(kk[3], "kda_wg") if cfg.kda_channel_gate
                   else {"kda_wg": dense(kk[3], (d, n_q), d, quantizable=False)}),
                "kda_o_norm": norm_init((cfg.kda_head_dim,)),
                "wo": dense(k[3], (hk, d), hk),
                "mlp_norm": norm_init((d,)),
            }
        elif cfg.kv_lora_rank:
            layer = latent_attention(k)
        else:
            layer = {
                "attn_norm": norm_init((d,)),
                "wq": dense(k[0], (d, n_q * hd), d),
                "wk": dense(k[1], (d, n_kv * hd), d),
                "wv": dense(k[2], (d, n_kv * hd), d),
                "wo": dense(k[3], (n_q * hd, d), n_q * hd),
                "mlp_norm": norm_init((d,)),
            }
        if cfg.n_experts and i >= cfg.first_k_dense:
            layer.update(routed_ffn(k, keys[i]))
        else:
            layer.update(dense_ffn(k))
        if cfg.qkv_bias and "wq" in layer:
            layer["bq"] = jnp.zeros((n_q * hd,), cfg.dtype)
            layer["bk"] = jnp.zeros((n_kv * hd,), cfg.dtype)
            layer["bv"] = jnp.zeros((n_kv * hd,), cfg.dtype)
        if cfg.qk_norm and "wq" in layer:
            layer["q_norm"] = norm_init((hd,))
            layer["k_norm"] = norm_init((hd,))
        # (keys folded in, as ``routed_ffn``'s: older trees stay what they were)
        if cfg.attn_output_gate and "wq" in layer:
            layer["wg"] = dense(
                jax.random.fold_in(keys[i], 4), (d, n_q * hd), d
            )
        if cfg.sandwich_norm:
            layer["attn_post_norm"] = norm_init((d,))
            layer["mlp_post_norm"] = norm_init((d,))
        if cfg.layer_kind(i) == "sliding":
            layer["window"] = jnp.asarray(cfg.sliding_window, jnp.int32)
        if cfg.router_before_attention and "router" in layer:
            layer["preroute"] = jnp.asarray(1, jnp.int32)
        layers.append(layer)

    params: Params = {
        # Embedding stays unquantized (gather path; tighter error budget).
        "embed": dense(keys[-2], (cfg.vocab_size, d), d, quantizable=False),
        "final_norm": norm_init((d,)),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(keys[-1], (d, cfg.vocab_size), d)
    return params


def init_kv_pages(
    cfg: LlamaConfig,
    total_pages: int,
    page_size: int,
    kv_quant_hbm: Optional[str] = None,
    sharding=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed K and V page pools:
    ``[n_layers, total_pages, page_size, n_kv_heads, head_dim]``; for a
    latent model (``cfg.kv_lora_rank``) one pool of latent rows
    ``[n_layers, total_pages, page_size, row]`` and an array of no pages.

    With ``kv_quant_hbm="int8"`` the pools hold int8 codes (half the HBM
    bytes per page — 2× pages per chip at the same budget); the matching
    per-page scale pools come from :func:`init_kv_scales`. ``sharding``
    (a ``Sharding`` or ``Device``) creates the pools in place there;
    default: the process default device."""
    dtype = jnp.int8 if kv_quant_hbm == "int8" else cfg.dtype
    if cfg.kv_lora_rank:
        # One pool of latent rows ``[n_layers, total_pages, page_size,
        # row]`` (``kv_row_shape``); the second of the pair every signature
        # carries is an array of no pages: it holds no byte and nothing
        # reads or writes it.
        if kv_quant_hbm is not None:
            raise ValueError("a latent pool has no quantised form")
        row = cfg.kv_row_shape
        return (
            jnp.zeros((cfg.n_attn_layers, total_pages, page_size, *row),
                      dtype, device=sharding),
            jnp.zeros((cfg.n_attn_layers, 0, page_size, *row), dtype,
                      device=sharding),
        )
    # the layer axis counts the layers that attend (every one, unless the
    # model has convolution layers); a row is ``kv_row_shape``
    shape = (cfg.n_attn_layers, total_pages, page_size, *cfg.kv_row_shape)
    return (
        jnp.zeros(shape, dtype, device=sharding),
        jnp.zeros(shape, dtype, device=sharding),
    )


def init_window_pages(
    cfg: LlamaConfig, window_pages: int, page_size: int, sharding=None
) -> Optional[tuple[jnp.ndarray, jnp.ndarray]]:
    """The zeroed K and V window pools of a model with sliding layers,
    ``[sliding layers, window_pages, page_size, n_kv_heads, head_dim]`` each,
    with page ids of their own (page 0 reserved, as in the key/value pools);
    None for a model without such layers. Every program takes and returns
    the pair as ONE argument, ``window_pages``."""
    if not cfg.n_window_layers:
        return None
    shape = (cfg.n_window_layers, window_pages, page_size, *cfg.kv_row_shape)
    return (
        jnp.zeros(shape, cfg.dtype, device=sharding),
        jnp.zeros(shape, cfg.dtype, device=sharding),
    )


def init_state_pages(
    cfg: LlamaConfig, total_pages: int, sharding=None
) -> Optional[jnp.ndarray]:
    """The zeroed state pool of a model with convolution layers, ``[conv
    layers, total_pages, (conv_L_cache - 1) * hidden]``, addressed by the
    key/value pools' page ids; None for a model without such layers.

    Slot ``p`` of a layer holds that layer's state after the last token
    written in page ``p``: the ``conv_L_cache - 1`` newest rows of ``z = B *
    x``, oldest first. A full page's slot is therefore the snapshot at the
    page's end: the first token of the next page reads it through its block
    table, whether the page was written by this sequence or is a prefix-
    cache hit, and a page id that is evicted and reused takes its state with
    it. The rows of a slot lie side by side in one pool row: a ``[..., 2,
    2048]`` minor shape would be padded to whole sublane tiles in HBM."""
    if not cfg.n_conv_layers:
        return None
    return jnp.zeros(
        (cfg.n_conv_layers, total_pages, cfg.state_row), cfg.dtype,
        device=sharding,
    )


def init_kda_state(
    cfg: LlamaConfig, slots: int, sharding=None
) -> Optional[tuple[jnp.ndarray, jnp.ndarray]]:
    """The zeroed state pool of a model with linear-attention layers, a
    pair every program takes and returns as ONE argument, ``state_pages``:
    the heads' matrices ``[linear layers, slots, n_heads, K, V]`` float32 and
    the carried convolution rows ``[linear layers, slots, *kda_conv_tile]``
    in the model's dtype (the ``kda_conv_kernel - 1`` newest rows of ``[q | k
    | v]`` before the convolution, oldest first, side by side: a slot's
    ``kda_conv_row`` values as whole tiles that lie together). None for a
    model without such layers.

    A slot is no page's: ``server/block_manager.py``'s ``StatePool`` hands
    them out, one live slot a sequence and snapshots beside them in the same
    arrays. A program reads a row's state from one slot and writes it to
    another (or the same): slot 0 is reserved for the rows that hold no
    sequence."""
    if not cfg.n_kda_layers:
        return None
    hk = cfg.kda_head_dim
    return (
        jnp.zeros((cfg.n_kda_layers, slots, cfg.n_heads, hk, hk), jnp.float32,
                  device=sharding),
        jnp.zeros((cfg.n_kda_layers, slots, *cfg.kda_conv_tile), cfg.dtype,
                  device=sharding),
    )


def init_kv_scales(
    cfg: LlamaConfig, total_pages: int, sharding=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed per-page-per-(layer, kv_head) f32 scale pools
    ``[n_layers, total_pages, n_kv_heads]`` for an int8 HBM KV pool
    (``KV_QUANT_HBM=int8``). Zero scales dequantize to exact zeros, so a
    fresh quantized pool reads identically to the legacy zeroed bf16 pool."""
    shape = (cfg.n_layers, total_pages, cfg.n_kv_heads)
    return (
        jnp.zeros(shape, jnp.float32, device=sharding),
        jnp.zeros(shape, jnp.float32, device=sharding),
    )


def _own_part(cfg: LlamaConfig):
    """``[n_heads, kv_heads_per_row]`` bool: which part of its KV head's
    pool row a query head's own KV head lies in."""
    g = cfg.kv_heads_per_row
    kv_head = jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)
    return (kv_head % g)[:, None] == jnp.arange(g)[None, :]


def _pack_heads(cfg: LlamaConfig, q, k, v):
    """Queries, keys and values ``[..., heads, hd]`` as the kernels take
    them over a pool whose row holds ``g = kv_heads_per_row`` KV heads
    (``kv_row_shape``): ``n_kv / g`` KV heads of ``g * hd`` lanes, and each
    query head its ``hd`` values in the part of the row where its own KV
    head lies, zeros in the others. Its scores are then its own head's (at
    the caller's ``scale``, ``hd ** -0.5``), and its own part of the
    kernel's output its result (``_unpack_heads``): ``g`` times the score
    FLOPs, no byte more, both kernels as they are."""
    g = cfg.kv_heads_per_row
    if g == 1:
        return q, k, v
    own = _own_part(cfg)[:, :, None]
    q = jnp.where(own, q[..., None, :], 0).reshape(*q.shape[:-1], g * cfg.hd)
    row = cfg.kv_row_shape
    return q, k.reshape(*k.shape[:-2], *row), v.reshape(*v.shape[:-2], *row)


def _unpack_heads(cfg: LlamaConfig, out):
    """A packed kernel's output ``[..., n_heads, g * hd]``: each query
    head's own part, ``[..., n_heads, hd]``."""
    g = cfg.kv_heads_per_row
    if g == 1:
        return out
    out = out.reshape(*out.shape[:-1], g, cfg.hd)
    return jnp.sum(jnp.where(_own_part(cfg)[:, :, None], out, 0), axis=-2)


def _head_pool(cfg: LlamaConfig, pool_l):
    """One layer of a key/value pool with a row a KV head: ``[pages,
    page_size, n_kv, hd]`` (the ``xla`` prefill reads it so)."""
    if cfg.kv_heads_per_row == 1:
        return pool_l
    return pool_l.reshape(*pool_l.shape[:2], cfg.n_kv_heads, cfg.hd)


def _qkv(layer: Params, cfg: LlamaConfig, x: jnp.ndarray):
    b, s, d = x.shape
    q = x @ _w(layer["wq"], x.dtype)
    k = x @ _w(layer["wk"], x.dtype)
    v = x @ _w(layer["wv"], x.dtype)
    if cfg.qkv_bias:
        q = q + layer["bq"]
        k = k + layer["bk"]
        v = v + layer["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    return q, k, v


def _rotates(layer: Params, cfg: LlamaConfig) -> bool:
    """Whether a layer that attends rotates q and k: every one, except the
    full layers of a model that has sliding ones (they take no positions),
    and none of a model without positions (``no_rope``)."""
    return not cfg.no_rope and ("window" in layer or not cfg.sliding_window)


def _attn_gate(layer: Params, x: jnp.ndarray, heads: jnp.ndarray):
    """The heads' output ``[..., n_heads * hd]`` times ``sigmoid(x wg)`` where
    the layer has an output gate; as it came where it has none."""
    if "wg" not in layer:
        return heads
    gate = jax.nn.sigmoid((x @ _w(layer["wg"], x.dtype)).astype(jnp.float32))
    return (heads.astype(jnp.float32) * gate).astype(heads.dtype)


def _post_norm(layer: Params, cfg: LlamaConfig, name: str, out: jnp.ndarray):
    """A sandwich norm (``attn_post_norm`` / ``mlp_post_norm``) on what a
    half of the layer adds to the residual, where the layer has it."""
    if name not in layer:
        return out
    return rms_norm(out, layer[name], cfg.rms_norm_eps, cfg.norm_offset)


# -- latent attention (MLA) ---------------------------------------------------
def _deinterleave(x: jnp.ndarray) -> jnp.ndarray:
    """``[x0, x1, x2, ...] -> [x0, x2, ..., x1, x3, ...]`` on the last axis:
    what the published code does before it rotates halves, so that the pairs
    ``(2i, 2i+1)`` are the ones rotated (``rope_interleave``). Applied to
    queries and keys alike, the permutation leaves every score unchanged."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _mla_project(layer: Params, cfg: LlamaConfig, x, positions, inv_freq):
    """A latent layer's projections of ``x [b, s, d]`` at ``positions``:
    ``(q_n [b, s, H, d_n], q_r [b, s, H, d_r] rotated, row [b, s, width])``.
    ``row`` is the token's whole cache entry, ``[RMSNorm(c) | rotated k_r |
    zeros to the row's width]`` (``kv_row_shape``): after the norm (and the
    ``mla_scale_kv_lora`` factor, where the model has one) and after the
    rotation, nothing else is kept of a token. The query is ``x wq``, or,
    where the layer has the low-rank pair, ``RMSNorm(x wq_a) wq_b`` (times
    the ``mla_scale_q_lora`` factor, which commutes with ``wq_b``)."""
    b, s, _ = x.shape
    dc, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if "wq_a" in layer:
        # the low-rank query path: down, norm (and its factor), up
        q = rms_norm(
            x @ _w(layer["wq_a"], x.dtype), layer["q_a_norm"],
            cfg.rms_norm_eps, cfg.norm_offset,
        )
        if cfg.mla_scale_q_lora:
            q = q * jnp.asarray(
                (cfg.hidden_size / cfg.q_lora_rank) ** 0.5, q.dtype
            )
        q = q @ _w(layer["wq_b"], x.dtype)
    else:
        q = x @ _w(layer["wq"], x.dtype)
    q = q.reshape(b, s, cfg.n_heads, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    a = x @ _w(layer["wkv_a"], x.dtype)  # [b, s, dc + dr]
    c = rms_norm(a[..., :dc], layer["kv_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    if cfg.mla_scale_kv_lora:
        c = c * jnp.asarray((cfg.hidden_size / cfg.kv_lora_rank) ** 0.5, c.dtype)
    k_r = a[..., None, dc:]  # one key, shared by every head
    if cfg.rope_interleave:
        q_r, k_r = _deinterleave(q_r), _deinterleave(k_r)
    q_r = apply_rope(q_r, positions, inv_freq)
    k_r = apply_rope(k_r, positions, inv_freq)[:, :, 0]
    pad = cfg.kv_row_shape[0] - dc - dr
    row = jnp.concatenate(
        [c, k_r, jnp.zeros((b, s, pad), c.dtype)], axis=-1
    )
    return q_n, q_r, row


def _mla_kvb(layer: Params, cfg: LlamaConfig, dtype):
    """``W_kvb`` split a head: ``(W_kb [d_c, H, d_n], W_vb [d_c, H, d_v])``."""
    w = _w(layer["wkv_b"], dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


def _mla_scale(cfg: LlamaConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _mla_absorbed(
    layer: Params, cfg: LlamaConfig, q_n, q_r, row, pool, block_tables,
    ctx_lens, n_valid, *, layer_index, interpret: bool, kernel: bool = True,
) -> jnp.ndarray:
    """Absorbed form over the pool (``ops/mla_attention.py``): ``q_c = q_n
    W_kb^T``, every head scores ``[q_c | q_r]`` against the same row ``[c |
    k_r]`` and sums the rows' latents; ``W_vb`` after. No key or value of a
    head is ever made for a context token. ``kernel``: the Pallas kernel
    over the pool in place; else its ``jax.numpy`` oracle over the layer's
    gathered pages (the ``attn_impl="xla"`` prefill of CPU tests and dry
    runs: its temporaries grow with rows x context x heads, so it is no
    serving path at long contexts). Both take the chunk as consecutive
    positions, right-padded. Returns the heads' outputs ``[b, s, H, d_v]``."""
    from ..ops.mla_attention import (
        mla_paged_attention,
        mla_paged_attention_reference,
    )

    w_kb, w_vb = _mla_kvb(layer, cfg, q_n.dtype)
    q_c = jnp.einsum("bshn,chn->bshc", q_n, w_kb)
    pad = row.shape[-1] - q_c.shape[-1] - q_r.shape[-1]
    q = jnp.concatenate(
        [q_c, q_r, jnp.zeros((*q_r.shape[:-1], pad), q_r.dtype)], axis=-1
    )
    if kernel:
        o_c = mla_paged_attention(
            q, row, pool, block_tables, ctx_lens, n_valid,
            dv=cfg.kv_lora_rank, scale=_mla_scale(cfg), interpret=interpret,
            layer=jnp.int32(layer_index),
        )  # [b, s, H, d_c]
    else:
        o_c = mla_paged_attention_reference(
            q, row, pool[layer_index], block_tables, ctx_lens, n_valid,
            dv=cfg.kv_lora_rank, scale=_mla_scale(cfg),
        )
    return jnp.einsum("bshc,chv->bshv", o_c, w_vb)


# -- gated short convolution (LFM2) ------------------------------------------
def _conv_state_plan(start, n_valid, page_ids, page_size: int):
    """Where a chunk of consecutive positions leaves convolution state:
    ``(last [b, n], page [b, n], ok [b, n])``, one entry a page the chunk can
    touch. ``last`` is the chunk index of the last VALID token in the row's
    n-th touched page (right-padded rows leave the state of their last real
    token, not of their padding), ``page`` that page's id and ``ok`` whether
    the row has a valid token there. ``start [b]`` is the position of chunk
    index 0 (it may lie inside a page), ``n_valid [b]`` the real tokens."""
    s = page_ids.shape[1]
    n_touched = (s + page_size - 2) // page_size + 1
    first_page = start // page_size
    j = jnp.arange(n_touched)[None, :]
    page_end = (first_page[:, None] + j + 1) * page_size - 1 - start[:, None]
    page_begin = page_end - (page_size - 1)
    last = jnp.clip(jnp.minimum(page_end, n_valid[:, None] - 1), 0, s - 1)
    ok = page_begin < n_valid[:, None]
    return last, jnp.take_along_axis(page_ids, last, axis=1), ok


@_scope("conv")
def _conv_prev_state(state_pages, cfg: LlamaConfig, prev_page, has_prev):
    """Every convolution layer's state before a chunk's first token: the
    slot of the page holding the token before it (``prev_page [b]``), zeros
    where there is none (position 0). ``[conv layers, b, K - 1, d]``."""
    got = _slot_rows(state_pages, prev_page, has_prev)
    return got.reshape(*got.shape[:2], cfg.conv_L_cache - 1, cfg.hidden_size)


@_scope("conv")
def _conv_operator(layer: Params, cfg: LlamaConfig, x, state):
    """The gated short convolution over a chunk ``x [b, s, d]`` that follows
    ``state [b, K - 1, d]`` (the newest rows of ``z`` before it, oldest
    first; ``K = conv_L_cache``): ``[B | C | x'] = x conv_in``, ``z = B *
    x'``, ``c_t = sum_j conv_w[j] * z_{t - (K - 1) + j}``, ``y = C * c``,
    ``y conv_out``. No position enters. Returns (output ``[b, s, d]``, ``z``
    with the state before it ``[b, K - 1 + s, d]``: the state after chunk
    index ``i`` is its rows ``i + 1 .. i + K - 1``)."""
    s = x.shape[1]
    gate_b, gate_c, xp = jnp.split(x @ _w(layer["conv_in"], x.dtype), 3, axis=-1)
    z = jnp.concatenate([state.astype(x.dtype), gate_b * xp], axis=1)
    taps = layer["conv_w"].astype(jnp.float32)  # [K, d]
    conv = sum(
        taps[j] * z[:, j : j + s].astype(jnp.float32)
        for j in range(taps.shape[0])
    )
    y = gate_c * conv.astype(x.dtype)
    return y @ _w(layer["conv_out"], x.dtype), z


def _scatter_state_pages(state_pages, fresh, page, ok):
    """Write every convolution layer's new slots with one update (aliased
    into the donated pool, as ``_scatter_kv_pages_all_layers``): ``fresh
    [conv layers, b, n, row]`` to the flat slots ``layer * pages + page[b,
    n]``, dropped where not ``ok``."""
    n_layers, _, row = state_pages.shape
    return _scatter_slots(
        state_pages, fresh.reshape(n_layers, -1, row), page.reshape(-1),
        ok.reshape(-1),
    )


# -- delta-rule linear attention (KDA) ----------------------------------------
def _slot_rows(pool, slot, has=None):
    """Every layer's slot ``slot [b]`` of a state pool ``[layers, slots,
    ...]``, zeros where not ``has [b]`` (None: every row has one): ``[layers,
    b, ...]``. Read as flat slots, one index a (layer, row), as the write is:
    with the layer axis in the gather's window (``pool[:, slot]``) the TPU
    compiler copies the whole pool into a layer-inward layout first
    (``python -m tools.aot_pool_copies --config lfm2-8b-a1b``)."""
    n_layers, slots = pool.shape[:2]
    idx = (
        jnp.arange(n_layers, dtype=slot.dtype)[:, None] * slots
        + jnp.clip(slot, 0, slots - 1)[None, :]
    )
    got = pool.reshape(n_layers * slots, *pool.shape[2:])[idx]
    if has is None:
        return got
    return jnp.where(has.reshape(1, -1, *(1,) * (got.ndim - 2)), got, 0)


@_scope("cache_write")
def _scatter_slots(pool, fresh, slot, ok):
    """``fresh [layers, b, ...]`` into the slots ``slot [b]`` of every layer
    of a state pool with one update (aliased into the donated pool), dropped
    where not ``ok [b]``."""
    n_layers, slots = pool.shape[:2]
    idx = jnp.where(
        (ok & (slot < slots))[None, :],
        jnp.arange(n_layers, dtype=slot.dtype)[:, None] * slots + slot[None, :],
        n_layers * slots,
    )
    flat = pool.reshape(n_layers * slots, *pool.shape[2:])
    flat = flat.at[idx.reshape(-1)].set(
        fresh.reshape(-1, *pool.shape[2:]).astype(flat.dtype), mode="drop"
    )
    return flat.reshape(pool.shape)


def _l2norm(t: jnp.ndarray) -> jnp.ndarray:
    """A head's vector over its length (``use_qk_norm`` of the family)."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _low_rank(layer: Params, name: str, x: jnp.ndarray) -> jnp.ndarray:
    """``(x W_down) W_up`` of a linear layer's low-rank pair ``name``."""
    return (x @ _w(layer[name + "_down"], x.dtype)) @ _w(
        layer[name + "_up"], x.dtype)


def _kda_inputs(layer: Params, cfg: LlamaConfig, x, rows):
    """A linear layer's per-token operands for ``x [b, s, d]`` (the normed
    input) that follows the carried rows ``rows [b, taps - 1, 3 H K]`` (the
    newest inputs of the convolution before it, oldest first): ``(q, k, v, g
    [b, s, H, K], beta [b, s, H]`` float32, ``z [b, taps - 1 + s, 3 H K]``:
    the carried rows after chunk index ``i`` are its rows ``i + 1 .. i + taps
    - 1``). ``[q | k | v] = SiLU(conv(x kda_qkv))``, q and k l2-normed a head
    (q also over sqrt(K)); ``g`` the log decay, a channel of the key, from the
    gate's projection (the low-rank pair where the layer has ``kda_wf_down``,
    else ``kda_wf``) by the published form (``kda_safe_gate``); ``beta =
    sigmoid(x w_b)``, doubled under ``kda_neg_eigval``. No position enters."""
    b, s, _ = x.shape
    H, K = cfg.n_heads, cfg.kda_head_dim
    f32 = jnp.float32
    z = jnp.concatenate(
        [rows.astype(x.dtype), x @ _w(layer["kda_qkv"], x.dtype)], axis=1
    )
    taps = layer["kda_conv_w"].astype(f32)
    conv = jax.nn.silu(sum(
        taps[j] * z[:, j : j + s].astype(f32) for j in range(taps.shape[0])
    ))
    q, k, v = (t.reshape(b, s, H, K) for t in jnp.split(conv, 3, axis=-1))
    q, k = _l2norm(q) * K**-0.5, _l2norm(k)
    if "kda_wf_down" in layer:
        a = _low_rank(layer, "kda_wf", x)
    else:
        a = x @ _w(layer["kda_wf"], x.dtype)
    a = (a.astype(f32) + layer["kda_dt_bias"]).reshape(b, s, H, K)
    rate = jnp.exp(layer["kda_A_log"].astype(f32))[:, None]
    if cfg.kda_safe_gate:
        g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * a)
    else:
        g = -rate * jax.nn.softplus(a)
    beta = jax.nn.sigmoid((x @ layer["kda_wb"].astype(x.dtype)).astype(f32))
    if cfg.kda_neg_eigval:
        beta = 2.0 * beta
    return q, k, v, g, beta, z


def _kda_output(layer: Params, cfg: LlamaConfig, x, o):
    """The heads' outputs ``o [b, s, H, V]`` float32 to the residual's
    width: a head's RMSNorm, times ``sigmoid(x w_g)`` (one gate a head, or
    one a channel through the low-rank pair where the layer has
    ``kda_wg_down``), ``wo``."""
    b, s = o.shape[:2]
    o = rms_norm(o, layer["kda_o_norm"].astype(o.dtype), cfg.rms_norm_eps,
                 cfg.norm_offset)
    if "kda_wg_down" in layer:
        gate = jax.nn.sigmoid(
            _low_rank(layer, "kda_wg", x).astype(jnp.float32)
        ).reshape(o.shape)
    else:
        gate = jax.nn.sigmoid(
            (x @ layer["kda_wg"].astype(x.dtype)).astype(jnp.float32)
        )[..., None]
    o = (o * gate).astype(x.dtype).reshape(b, s, -1)
    return o @ _w(layer["wo"], x.dtype)


def _group_limited(choice: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """Group-limited routing on the scores that choose (``[..., E]``): the
    ``E`` outputs lie in ``n_group`` groups of neighbours, a group's score is
    the sum of its two largest, and outside the ``topk_group`` best groups a
    score is -inf, so that the top-k after it takes no expert there. One
    group: the scores as they came."""
    if cfg.n_group == 1:
        return choice
    if choice.shape[-1] % cfg.n_group or not 1 <= cfg.topk_group <= cfg.n_group:
        raise ValueError(
            f"n_group={cfg.n_group}, topk_group={cfg.topk_group} do not "
            f"divide the router's {choice.shape[-1]} outputs"
        )
    grouped = choice.reshape(*choice.shape[:-1], cfg.n_group, -1)
    best = jnp.sum(jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0], axis=-1)
    _, kept = jax.lax.top_k(best, cfg.topk_group)
    keep = jnp.any(
        kept[..., :, None] == jnp.arange(cfg.n_group)[None, :], axis=-2
    )  # [..., n_group]
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(choice.shape)


def _preroute(layer: Params, cfg: LlamaConfig, h: jnp.ndarray):
    """The gates ``(top values, top indices)``, each ``[b, s, k]``, of a
    routed layer whose router reads the stream that enters the layer (the
    leaf ``preroute``): made from ``h`` before any norm and before the
    attention, under ``model.moe_preroute``, and handed to ``_ffn`` after it.
    None for every other layer, whose dispatch makes its own."""
    if "preroute" not in layer:
        return None
    with _scope("moe_preroute"):
        return _gates(layer, cfg, h)


@_scope("moe_router")
def _moe_gates(layer: Params, cfg: LlamaConfig, x: jnp.ndarray):
    """``_gates`` of the input the experts read, under ``model.moe_router``:
    every layer's routing but a pre-routed one's (``_preroute``)."""
    return _gates(layer, cfg, x)


def _gates(layer: Params, cfg: LlamaConfig, x: jnp.ndarray):
    """Top-k routing shared by both dispatch strategies.

    Gating matches HF Mixtral (`MixtralSparseMoeBlock`): softmax over ALL
    expert logits, take top-k, renormalize the survivors. Returns
    (top values [..., k] f32, top indices [..., k] int32). The indices are
    the router's own, over every output it scores (``cfg.router_outputs``:
    an id >= ``n_experts`` is a zero expert), whatever range of the experts
    this process holds.
    """
    router_logits = (x @ layer["router"]).astype(jnp.float32)  # [..., E]
    if cfg.moe_scoring == "sigmoid":
        # DeepSeek-V3's ``noaux_tc``: the k largest of score + correction
        # bias, among the experts of the best groups (``_group_limited``),
        # are CHOSEN; the gates are the scores alone (never the bias),
        # renormalised, times the scaling factor.
        scores = jax.nn.sigmoid(router_logits)
        _, topi = jax.lax.top_k(
            _group_limited(scores + layer["router_bias"], cfg),
            cfg.n_experts_per_tok,
        )
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.norm_topk_prob:
            topv = topv / (
                jnp.sum(topv, axis=-1, keepdims=True) + cfg.router_norm_eps
            )
        return topv * cfg.routed_scaling_factor, topi
    if cfg.moe_scoring != "softmax":
        raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
    weights = jax.nn.softmax(router_logits, axis=-1)
    if "router_bias" in layer:
        # LongCat-Flash: chosen by probability + correction bias, weighed
        # by the probability alone
        _, topi = jax.lax.top_k(
            _group_limited(weights + layer["router_bias"], cfg),
            cfg.n_experts_per_tok,
        )
        topv = jnp.take_along_axis(weights, topi, axis=-1)
    elif cfg.n_group != 1:
        _, topi = jax.lax.top_k(
            _group_limited(weights, cfg), cfg.n_experts_per_tok
        )
        topv = jnp.take_along_axis(weights, topi, axis=-1)
    else:
        topv, topi = jax.lax.top_k(weights, cfg.n_experts_per_tok)
    if cfg.norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        topv = topv * cfg.routed_scaling_factor
    return topv, topi


def _moe_mlp_dense(
    layer: Params, cfg: LlamaConfig, x: jnp.ndarray,
    gates: Optional[tuple] = None,
) -> jnp.ndarray:
    """Masked-dense sparse-MoE SwiGLU FFN (the numerics oracle).

    The combine is a masked-dense einsum over stacked expert weights
    ``[E, d, f]`` — every expert sees every token, with non-selected
    contributions zeroed by the gate. Exact, with TPU-native static shapes;
    under expert-parallel sharding (``E`` on the ``tp``/ep axis,
    `parallel/sharding.py`) each device only computes its LOCAL experts for
    the replicated activations and the final contraction over ``E`` becomes
    an XLA-inserted psum over ICI. With E == tp (Mixtral 8x7B on a v5e-8
    slice) per-device work is exactly one expert per token — but at
    E >> top-k (Qwen3-MoE's 128/8) it wastes ~E/k× expert FLOPs, which is
    what the routed dispatch below avoids. ``gates``: ``_preroute``'s, for
    a layer whose router does not read ``x``.
    """
    topv, topi = _moe_gates(layer, cfg, x) if gates is None else gates  # [b, s, k]
    with _scope("moe_experts"):
        # Scatter the renormalized top-k gates back to a dense [b, s, E] mask.
        gates = jnp.sum(
            jax.nn.one_hot(topi, cfg.n_experts, dtype=jnp.float32)
            * topv[..., None],
            axis=-2,
        )
        gate = cfg.act_fn(
            jnp.einsum(
                "bsd,edf->ebsf", x, _w(layer["w_gate"], x.dtype)
            ).astype(jnp.float32)
        )
        up = jnp.einsum(
            "bsd,edf->ebsf", x, _w(layer["w_up"], x.dtype)
        ).astype(jnp.float32)
        act = (gate * up).astype(x.dtype)
        return jnp.einsum(
            "ebsf,efd,bse->bsd", act, _w(layer["w_down"], x.dtype),
            gates.astype(x.dtype),
        )


def _grouped_dot(cfg: LlamaConfig, row_group_ids: jnp.ndarray, interpret: bool):
    """Grouped-matmul dispatcher for the routed MoE paths.

    Returns ``gdot(lhs, w, group_sizes)`` routing to the Pallas gmm kernel
    (``ops/gmm.py`` — megablox for bf16, in-VMEM-dequant for int8 expert
    stacks) per ``cfg.moe_gmm``, with ``jax.lax.ragged_dot`` as the XLA
    path/oracle. ``row_group_ids`` is the sorted expert id per row —
    needed to apply per-output-channel int8 scales on the kernel output.
    """
    from ..ops.gmm import grouped_matmul

    if cfg.moe_gmm not in ("auto", "kernel", "xla"):
        raise ValueError(f"unknown moe_gmm {cfg.moe_gmm!r}")
    use_kernel = cfg.moe_gmm == "kernel" or (
        cfg.moe_gmm == "auto" and not interpret
    )

    def gdot(lhs, w, group_sizes):
        if not isinstance(w, QuantizedTensor):
            w = _w(w, lhs.dtype)
        return grouped_matmul(
            lhs,
            w,
            group_sizes,
            row_group_ids=row_group_ids,
            interpret=interpret,
            use_kernel=use_kernel,
        )

    return gdot


#: sorted places a pass of the grouped matmuls of a layer that holds a share
#: of the experts (``_moe_mlp_routed``: a dispatch of more places than this
#: runs them a block at a time)
ROUTED_ROW_BLOCK = 4096


def _moe_mlp_routed(
    layer: Params, cfg: LlamaConfig, x: jnp.ndarray, interpret: bool = False,
    touched: Optional[list] = None, valid: Optional[jnp.ndarray] = None,
    held: Optional[tuple] = None, psum_axis: Optional[str] = None,
    gates: Optional[tuple] = None,
) -> jnp.ndarray:
    """Routed sparse-MoE SwiGLU FFN: grouped top-k gather dispatch.

    Per-token expert FLOPs scale with ``top-k``, not ``n_experts`` — the
    right complexity class for high-expert-count models (Qwen3-MoE 128/8:
    16× fewer expert FLOPs than the dense oracle). TPU-native shape
    discipline: all arrays are static-shaped in ``N*k``; the only dynamic
    structure is the per-expert segment boundaries, which
    ``jax.lax.ragged_dot`` consumes directly (tiled grouped matmul on MXU,
    no padding to per-expert capacity and no dropped tokens).

    Steps: flatten the (token, slot) assignments, sort them by expert id so
    each expert's tokens form one contiguous segment, run the three FFN
    matmuls as ragged (grouped) dots over those segments, then weight by
    the gate values and scatter-add back per token.

    ``touched``: a list the caller hands in while tracing, to which this
    layer's number of distinct experts chosen is appended (the experts
    whose weights the grouped dots read); None adds no operation. A layer
    that is told what it holds (below) appends ``[experts read, places on
    zero experts, places on held experts]`` instead.

    ``valid`` (``[b, s]`` bool, the mask a prefill body already has): a
    slot that holds no token chooses no expert. Its ``k`` places get the id
    one past the last held expert, so the stable sort puts them after every
    group, the group sizes sum to ``k`` times the real tokens and the
    grouped dots visit the real rows' tiles alone; what they leave past the
    last group is undefined on the kernel path and is selected to zero
    below. None (decode: every lane is a row) groups every row.

    ``held`` = (first, count): the layer's stacks are the experts ``first
    .. first + count - 1`` of the ``cfg.n_experts`` the router scores
    (``first`` may be traced: a shard's, under ``_moe_mlp_routed_ep``).
    Default: the configuration's own range, and None (no operation added)
    where that is every expert and the model has no zero expert. A place
    whose expert is held elsewhere takes the id past the last group, as a
    slot without a token does, and adds nothing: what the absent experts
    would add is left out. A place on a zero expert (id >= ``n_experts``)
    adds ``gate x input`` by one multiply-add (``model.moe_zero``) and
    reaches no grouped matmul.

    ``psum_axis``: sum the float32 result over that mesh axis before it is
    cast (the expert-parallel combine).

    ``gates``: the rows' ``(top values, top indices)`` where the layer's
    router does not read ``x`` (``_preroute``: made before the attention);
    None: the router reads ``x`` here. What follows is the same either way.
    """
    b, s, d = x.shape
    n = b * s
    k = cfg.n_experts_per_tok
    xf = x.reshape(n, d)
    if gates is None:
        topv, topi = _moe_gates(layer, cfg, xf)  # [n, k]
    else:
        topv, topi = (g.reshape(n, k) for g in gates)
    if held is None and not cfg.holds_every_expert:
        if cfg.expert_first + cfg.experts_held > cfg.n_experts:
            raise ValueError(
                f"experts {cfg.expert_first}..+{cfg.experts_held} lie past "
                f"the {cfg.n_experts} the router scores"
            )
        held = (cfg.expert_first, cfg.experts_held)
    n_held = cfg.n_experts if held is None else held[1]
    if layer["w_gate"].shape[0] != n_held:
        raise ValueError(
            f"the layer holds {layer['w_gate'].shape[0]} experts, the "
            f"dispatch was told {n_held}"
        )

    with _scope("moe_router"):
        zero_gate = None
        if held is not None:
            local = topi - held[0]
            # (the range lies inside the routed experts, so a zero
            # expert's id, >= n_experts, is never in it)
            here = (local >= 0) & (local < n_held)
            if cfg.n_zero_experts:
                on_zero = topi >= cfg.n_experts
                if valid is not None:
                    on_zero = on_zero & valid.reshape(n, 1)
                zero_gate = jnp.sum(jnp.where(on_zero, topv, 0.0), axis=-1)
            topi = jnp.where(here, local, n_held)
        if valid is not None:
            topi = jnp.where(valid.reshape(n, 1), topi, n_held)
        expert_ids = topi.reshape(-1)  # [n*k]
        token_ids = jnp.arange(n * k, dtype=jnp.int32) // k
        order = jnp.argsort(expert_ids, stable=True)
        # bincount drops ids >= length: the padding is in no group
        group_sizes = jnp.bincount(expert_ids, length=n_held)
        if touched is not None:
            n_touched = jnp.sum(group_sizes > 0, dtype=jnp.int32)
            if held is not None:
                n_touched = jnp.stack([
                    n_touched,
                    jnp.sum(on_zero, dtype=jnp.int32) if cfg.n_zero_experts
                    else jnp.zeros((), jnp.int32),
                    jnp.sum(group_sizes, dtype=jnp.int32),
                ])
            touched.append(n_touched)
        grouped_all = valid is None and held is None

    def expert_rows(rows, sizes, row_real=None):
        """The experts' part of the sorted places ``rows`` (indices into
        the ``n*k`` places, expert-contiguous, ``sizes`` of them an expert):
        (the token each came from, its weighted output [rows, d] f32).
        ``row_real``: which rows are in a group, where the places' own ids
        do not say (a block's padding)."""
        with _scope("moe_router"):
            src_tok = token_ids[rows]  # token each sorted row came from
            xs = xf[src_tok]  # [rows, d] gathered inputs, expert-contiguous
            sorted_ids = expert_ids[rows]
            if not grouped_all:
                if row_real is None:  # every row of a group is real
                    row_real = sorted_ids < n_held
                # the int8 experts' scales are gathered by this id
                sorted_ids = jnp.minimum(sorted_ids, n_held - 1)
        with _scope("moe_experts"):
            gdot = _grouped_dot(cfg, sorted_ids, interpret)
            gate = cfg.act_fn(
                gdot(xs, layer["w_gate"], sizes).astype(jnp.float32)
            )
            up = gdot(xs, layer["w_up"], sizes).astype(jnp.float32)
            act = (gate * up).astype(x.dtype)
            out = gdot(act, layer["w_down"], sizes)  # [rows, d]

            out = out.astype(jnp.float32) * topv.reshape(-1)[rows][:, None]
            if row_real is not None:
                # selected, not multiplied: megablox leaves these rows as it
                # found them, and 0 x NaN in a padded row would reach real
                # rows through attention's ``p @ V``
                out = jnp.where(row_real[:, None], out, 0.0)
            return src_tok, out

    block = ROUTED_ROW_BLOCK
    if cfg.holds_every_expert or n * k <= block:
        src_tok, out = expert_rows(order, group_sizes)
        with _scope("moe_experts"):
            combined = jnp.zeros((n, d), jnp.float32).at[src_tok].add(out)
    else:
        # A layer that holds a share of the experts groups a small part of
        # a prefill's places (16 of 768 outputs: a fiftieth), all of them
        # first in the sorted order: the grouped matmuls run over blocks of
        # sorted rows, as many as hold a row of a group, so the gathered
        # inputs and the experts' outputs are a block's and not the ``n*k``
        # places' (110 592 x 6144 values a fill dispatch at the published
        # widths, which no chip holds beside the model). Exact: no place
        # is dropped, whatever the router chose.
        with _scope("moe_router"):
            order_p = jnp.pad(order, (0, -(n * k) % block))
            starts = jnp.cumsum(group_sizes) - group_sizes
            total = jnp.sum(group_sizes)

        def one_block(i, combined):
            lo = i * block
            with _scope("moe_router"):
                rows = jax.lax.dynamic_slice(order_p, (lo,), (block,))
                sizes = (
                    jnp.clip(starts + group_sizes - lo, 0, block)
                    - jnp.clip(starts - lo, 0, block)
                )
                row_real = lo + jnp.arange(block) < total
            src_tok, out = expert_rows(rows, sizes, row_real)
            with _scope("moe_experts"):
                return combined.at[src_tok].add(out)

        combined = jax.lax.fori_loop(
            0, (total + block - 1) // block, one_block,
            jnp.zeros((n, d), jnp.float32),
        )
    if zero_gate is not None:
        with _scope("moe_zero"):
            # the identity experts: their gates' sum times the token itself
            combined = combined + zero_gate[:, None] * xf.astype(jnp.float32)
    with _scope("moe_experts"):
        if psum_axis is not None:
            combined = jax.lax.psum(combined, psum_axis)
        return combined.reshape(b, s, d).astype(x.dtype)


def _moe_mlp_routed_ep(
    layer: Params, cfg: LlamaConfig, x: jnp.ndarray, mesh,
    interpret: bool = False, gates: Optional[tuple] = None,
) -> jnp.ndarray:
    """Expert-parallel routed dispatch under ``shard_map`` over the tp axis.

    GSPMD cannot partition ``ragged_dot``'s group dimension, so the global
    routed pipeline under a mesh would silently all-gather the full
    ``[E, d, f]`` expert stacks — the exact HBM blow-up expert parallelism
    exists to avoid. Here each shard holds ``E/tp`` whole experts
    (matching ``parallel/sharding.py``'s ``P('tp', None, None)`` layout)
    and runs the single-shard dispatch told its own range (``held``: every
    shard gates over ALL experts with the replicated router and its bias,
    a place held by another shard is in no group here and adds nothing);
    the per-token combine is a psum over ICI. Shapes stay static with no
    capacity factor and NO dropped tokens; the grouped dots visit the
    tiles of the shard's own rows alone. ``_moe_mlp`` selects this path
    whenever ``k*tp < E`` (Qwen3-MoE 128/8 at tp=8). ``gates``
    (``_preroute``'s) ride in beside the rows they belong to.
    """
    from jax.sharding import PartitionSpec as P

    if not cfg.holds_every_expert:
        raise ValueError(
            "tp > 1 over a held range of the experts, or with zero "
            "experts, is not run"
        )
    e_local = cfg.n_experts // mesh.shape["tp"]
    # Batch stays sharded over dp when the mesh has a dp axis (training);
    # activations are replicated across tp either way.
    batch_axis = "dp" if "dp" in mesh.shape else None

    def body(router, w_gate, w_up, w_down, xs, made):
        # QuantizedTensor expert shards flow into the gmm kernel as-is
        # (specs are pytree prefixes, so q and scale both shard on E);
        # the kernel dequantizes per-tile in VMEM.
        shard = {**router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        return _moe_mlp_routed(
            shard, cfg, xs, interpret,
            held=(jax.lax.axis_index("tp") * e_local, e_local),
            psum_axis="tp", gates=made,
        )

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),
            P("tp", None, None),
            P("tp", None, None),
            P("tp", None, None),
            P(batch_axis),
            P(batch_axis),  # a prefix of ``gates``: None has no leaf
        ),
        out_specs=P(batch_axis),
        check_vma=False,
    )
    router = {k: layer[k] for k in ("router", "router_bias") if k in layer}
    return fn(
        router, layer["w_gate"], layer["w_up"], layer["w_down"], x, gates
    )


def _moe_mlp(
    layer: Params, cfg: LlamaConfig, x: jnp.ndarray, mesh=None,
    interpret: bool = False, touched: Optional[list] = None,
    valid: Optional[jnp.ndarray] = None, gates: Optional[tuple] = None,
) -> jnp.ndarray:
    # ``valid`` reaches the single-shard routed dispatch alone: the
    # expert-parallel one (``tp`` > 1) and the dense oracle compute every
    # row, padding included, as they always have. ``gates`` (``_preroute``'s)
    # reach all three.
    if cfg.moe_dispatch not in ("routed", "dense"):
        raise ValueError(f"unknown moe_dispatch {cfg.moe_dispatch!r}")
    if cfg.moe_dispatch == "dense" and not cfg.holds_every_expert:
        raise ValueError(
            'moe_dispatch="dense" scores every expert against every token: '
            "a held range of the experts, or zero experts, takes \"routed\""
        )
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp > 1:
        if cfg.n_experts % tp == 0:
            # Expert-parallel mesh (weights laid out P('tp', None, None)).
            # Routed-EP computes n*k rows per shard vs dense-EP's n*E/tp —
            # auto-select whichever does less per-shard work; both exact.
            if (
                cfg.moe_dispatch == "routed"
                and cfg.n_experts_per_tok * tp < cfg.n_experts
            ):
                return _moe_mlp_routed_ep(layer, cfg, x, mesh, interpret, gates)
            return _moe_mlp_dense(layer, cfg, x, gates)
        # E % tp != 0: weights use the Megatron intermediate-dim fallback
        # (sharding.py). The global routed path would make GSPMD all-gather
        # the full expert stacks, so ALWAYS use the dense einsum here —
        # GSPMD partitions it along the f dimension.
        return _moe_mlp_dense(layer, cfg, x, gates)
    if cfg.moe_dispatch == "routed":
        return _moe_mlp_routed(
            layer, cfg, x, interpret, touched, valid, gates=gates
        )
    return _moe_mlp_dense(layer, cfg, x, gates)


def _mlp(
    layer: Params, cfg: LlamaConfig, x: jnp.ndarray, mesh=None,
    interpret: bool = False, touched: Optional[list] = None,
    valid: Optional[jnp.ndarray] = None, gates: Optional[tuple] = None,
) -> jnp.ndarray:
    # The layer says what its FFN is (it has a router or it has not), never
    # its index: a layer run alone (the benchmark's comparison) or a model
    # with leading dense layers is served by what its parameters hold.
    if ("preroute" in layer) != (gates is not None):
        raise ValueError(
            "a layer whose router reads the layer's input (and no other) "
            "takes its gates from the body that runs it: _preroute, before "
            "the attention"
        )
    if "router" in layer:
        out = _moe_mlp(
            layer, cfg, x, mesh=mesh, interpret=interpret, touched=touched,
            valid=valid, gates=gates,
        )
        if "ws_gate" in layer:
            # the shared experts: one SwiGLU every token takes, a plain
            # matmul beside the grouped ones
            with _scope("moe_shared"):
                out = out + _swiglu(
                    cfg, x, layer["ws_gate"], layer["ws_up"], layer["ws_down"]
                )
        return out
    with _scope("ffn"):
        return _swiglu(cfg, x, layer["w_gate"], layer["w_up"], layer["w_down"])


def _sublayers(layers):
    """The (attention, FFN) pairs of ``layers`` in the order they run: a
    layer itself, then the ``second`` half of one that has it (a double
    layer). What each is is still read from its own parameters."""
    for layer in layers:
        yield layer
        if "second" in layer:
            yield layer["second"]


def _ffn(
    layer: Params, cfg: LlamaConfig, h: jnp.ndarray, mesh=None,
    interpret: bool = False, touched: Optional[list] = None,
    valid: Optional[jnp.ndarray] = None, aside: Optional[list] = None,
    gates: Optional[tuple] = None,
) -> jnp.ndarray:
    """A layer's second half as every body runs it: ``h + _mlp(mlp_norm(h))``.
    The norm lies under the scope of what reads it (``model.moe_router``
    for a routed layer, else ``model.ffn``), the residual under that of what
    it adds (``model.moe_experts``, else ``model.ffn``).

    ``aside`` (a list the body keeps over its layer loop) is the one piece
    that carries a double layer's routed sum: a layer with a ``moe`` part
    (the first half) also runs that routed FFN on its normed input and puts
    the result there; the next FFN that has none (the second half) adds it
    after its own: ``out = c + FFN_1(y) + MoE(x)``.

    ``gates``: what ``_preroute`` made of the stream that entered the layer,
    for a layer whose router reads that (None for every other)."""
    routed = "router" in layer
    with _scope("moe_router" if routed else "ffn"):
        x = rms_norm(h, layer["mlp_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    if "moe" in layer:
        aside.append(_mlp(
            layer["moe"], cfg, x, mesh=mesh, interpret=interpret,
            touched=touched, valid=valid,
        ))
    out = _mlp(
        layer, cfg, x, mesh=mesh, interpret=interpret, touched=touched,
        valid=valid, gates=gates,
    )
    with _scope("moe_experts" if routed else "ffn"):
        h = h + _post_norm(layer, cfg, "mlp_post_norm", out)
    if aside and "moe" not in layer:
        with _scope("moe_experts"):
            h = h + aside.pop()
    return h


def _swiglu(cfg: LlamaConfig, x, w_gate, w_up, w_down) -> jnp.ndarray:
    gate = cfg.act_fn((x @ _w(w_gate, x.dtype)).astype(jnp.float32))
    up = (x @ _w(w_up, x.dtype)).astype(jnp.float32)
    return ((gate * up).astype(x.dtype)) @ _w(w_down, x.dtype)


def _embed(params: Params, cfg: LlamaConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    emb = params["embed"]
    if isinstance(emb, QuantizedTensor):
        # Gather int8 rows, then scale — never materializes the bf16 table.
        h = emb.q[tokens].astype(cfg.dtype) * emb.scale[0].astype(cfg.dtype)
    else:
        h = emb[tokens]
    if cfg.scale_embeddings:  # Gemma: normalizer folded out of the table
        h = h * jnp.asarray(cfg.hidden_size**0.5, h.dtype)
    return h


@_scope("head")
def _logits(params: Params, cfg: LlamaConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    head = (
        _w(params["embed"], h.dtype).T
        if cfg.tie_word_embeddings
        else _w(params["lm_head"], h.dtype)
    )
    return (h @ head).astype(jnp.float32)


@_scope("cache_write")
def _scatter_kv_pages_all_layers(
    pages: jnp.ndarray,  # [n_layers, total_pages, page_size, n_kv, hd]
    fresh: jnp.ndarray,  # [n_layers, b, s, n_kv, hd] (or [n_layers, b*s, ...])
    page_ids: jnp.ndarray,  # [b, s]
    slot_ids: jnp.ndarray,  # [b, s]
    valid: jnp.ndarray,  # [b, s]
) -> jnp.ndarray:
    """Scatter every layer's fresh K or V into the pool with ONE update op
    (aliased into the donated buffer; invalid positions dropped): the one
    write path of ``prefill``, ``decode_step(s)``, ``spec_decode_steps``
    and ``denoise_step(s)``.

    The pool is written as flat token rows ``[n_layers * total_pages *
    page_size, n_kv, hd]``, one index a (layer, token), layer-major like
    the stacked fresh K/V, so that the scatter's window is the ``[n_kv,
    hd]`` slice alone. With the layer axis in the window (``pages.at[:,
    page, slot]``, as this was until PR 29) and fewer than 8 KV heads, the
    TPU compiler lays the operand out with the layers on the sublanes
    (``{4,0,3,2,1:T(8,128)}``) and copies the whole pool into that layout
    and back around the scatter: four copies of a 512 MiB pool, 6.5-6.7 ms
    of every MoE decode step and denoising forward on a v5e
    (PERF_LEDGER.jsonl, PR 28: ``copy_bf16_8_4096_16_4_128_``;
    ``%copy.2295``/``.2305`` in, ``%copy.2306``/``.2307`` out). No CPU test
    can see that: the compiled program is read in
    ``tests/test_pool_layout.py``, which fails if any instruction but this
    scatter's fusion produces an array of the pool's shape."""
    L, total_pages, page_size, *row = pages.shape  # row: [n_kv, hd] or [width]
    layer_rows = total_pages * page_size
    # A token that is not valid, or whose page or slot lies past the pool's
    # (as a flat row it would be a real row of the next page or layer), gets
    # the first row past the pool, which mode="drop" discards. (int32: a
    # pool of 2**31 token rows is beyond any HBM.)
    keep = valid & (page_ids < total_pages) & (slot_ids < page_size)
    rows = (page_ids * page_size + slot_ids).reshape(-1)
    layer_base = jnp.arange(L, dtype=rows.dtype) * layer_rows
    rows = jnp.where(
        keep.reshape(1, -1), layer_base[:, None] + rows[None, :], L * layer_rows
    )
    flat = pages.reshape(L * layer_rows, *row)
    flat = flat.at[rows.reshape(-1)].set(fresh.reshape(-1, *row), mode="drop")
    return flat.reshape(pages.shape)


@_scope("cache_write")
def _quantized_scatter_kv_all_layers(
    pages_q: jnp.ndarray,  # [n_layers, total_pages, page_size, n_kv, hd] int8
    scales: jnp.ndarray,  # [n_layers, total_pages, n_kv] f32
    fresh: jnp.ndarray,  # [n_layers, b, s, n_kv, hd]
    page_ids: jnp.ndarray,  # [b, s]
    slot_ids: jnp.ndarray,  # [b, s]
    valid: jnp.ndarray,  # [b, s]
    positions: jnp.ndarray,  # [b, s] absolute positions of the written tokens
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write-time quantization (``KV_QUANT_HBM=int8``): the int8 analogue of
    :func:`_scatter_kv_pages_all_layers`, maintaining the per-page-per-
    (layer, kv_head) symmetric scales as it writes.

    Engine contracts this leans on: chunk positions are consecutive, the
    valid mask is a right-padded prefix, and no page is shared between rows.
    So the only page that can already hold live codes is each row's FIRST
    page, and only when the row's first position is not page-aligned (the
    "carry" page — in practice the decode write at ``my_slot != 0``; engine
    prefill chunks start page-aligned). Every other written page is fresh:
    its scale resets to zero before the scatter-max, so a previous tenant's
    scale can never inflate the new resolution. The carry page's resident
    codes are requantized under the grown scale with the exact ratio
    ``s_old / s_new`` — a bit-exact no-op when the scale is unchanged.

    The fresh codes are written as flat token rows, by the bf16 pool's own
    ``_scatter_kv_pages_all_layers``; the carry page's rewrite still has the layer
    axis in its scatter window, so with fewer than 8 KV heads the program
    compiled for a v5e still copies the whole pool around it (read with
    the recipe of ``tools/aot_pool_copies.py``; no benchmark cell runs an
    int8 pool, so not measured)."""
    L, P, ps, n_kv, hd = pages_q.shape
    b, s = page_ids.shape
    pidx = jnp.where(valid.reshape(-1), page_ids.reshape(-1), P)
    sidx = slot_ids.reshape(-1)
    x = fresh.reshape(L, b * s, n_kv, hd).astype(jnp.float32)

    row_valid = valid[:, 0]
    carry = (positions[:, 0] % ps) != 0
    carry_page = jnp.where(row_valid & carry, page_ids[:, 0], P)  # [b]

    # Fresh pages (everything written except each row's carry page): zero
    # their scales so the scatter-max below starts from a clean slate.
    fresh_page_mask = valid & (page_ids != carry_page[:, None])
    fresh_pidx = jnp.where(fresh_page_mask.reshape(-1), page_ids.reshape(-1), P)
    scales0 = scales.at[:, fresh_pidx].set(0.0, mode="drop")

    # Per-token symmetric scale candidates, scatter-maxed into the pages
    # (same floor/denominator as quant.quantize_kv_page).
    cand = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0  # [L, N, n_kv]
    new_scales = scales0.at[:, pidx].max(cand, mode="drop")

    # Requantize the carry page's resident codes under the grown scale.
    cp = jnp.minimum(carry_page, P - 1)  # clamped for the gather only
    old = pages_q[:, cp].astype(jnp.float32)  # [L, b, ps, n_kv, hd]
    s_old = scales[:, cp]  # [L, b, n_kv] — pre-update scales
    s_new = new_scales[:, cp]
    ratio = jnp.where(s_new > 0, s_old / jnp.maximum(s_new, 1e-30), 1.0)
    req = jnp.clip(
        jnp.round(old * ratio[:, :, None, :, None]), -127, 127
    ).astype(jnp.int8)
    pages_q = pages_q.at[:, carry_page].set(req, mode="drop")

    # Quantize the fresh tokens with their page's final scale and scatter.
    s_tok = new_scales[:, jnp.minimum(pidx, P - 1)]  # [L, N, n_kv]
    q = jnp.clip(
        jnp.round(x / jnp.maximum(s_tok, 1e-30)[..., None]), -127, 127
    ).astype(jnp.int8)
    pages_q = _scatter_kv_pages_all_layers(pages_q, q, page_ids, slot_ids, valid)
    return pages_q, new_scales


def _prefill_body(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, s] int32, right-padded
    positions: jnp.ndarray,  # [b, s] int32 absolute positions
    valid: jnp.ndarray,  # [b, s] bool, right-padded prefix mask
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_ids: jnp.ndarray,  # [b, s]
    slot_ids: jnp.ndarray,  # [b, s]
    block_tables: jnp.ndarray,  # [b, max_ctx_pages]
    ctx_lens: jnp.ndarray,  # [b]
    mesh,
    attn_impl: str,
    interpret: bool,
    k_scales=None,  # [L, P, n_kv] f32 when KV_QUANT_HBM=int8
    v_scales=None,
    experts_touched: Optional[list] = None,  # see ``_moe_mlp_routed``
    state_pages=None,  # ``init_state_pages``: a model with conv layers
    window_pages=None,  # ``init_window_pages``: a model with sliding layers
    window_rows=None,  # its rows' (page_ids [b, s], tables [b, w], starts [b])
    state_slots=None,  # [b, 2]: a model with linear layers (``prefill``)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Any, Any, Any, Any]:
    """Traced prefill shared by ``prefill``, the fused speculative-decode
    scan (``spec_decode_steps``) and ``_denoise_body``: the chunk's forward
    over the paged context (``_prefill_forward``), then its one write of
    the pools (``_prefill_write``). Returns (hidden states [b, s, d],
    k_pages, v_pages, k_scales, v_scales, state_pages, window_pages); logits
    selection stays with the caller."""
    h, fresh = _prefill_forward(
        params, cfg, tokens, positions, valid, k_pages, v_pages, page_ids,
        block_tables, ctx_lens, mesh, attn_impl, interpret, k_scales,
        v_scales, experts_touched, state_pages, window_pages, window_rows,
        state_slots,
    )
    return (h,) + _prefill_write(
        fresh, positions, valid, k_pages, v_pages, page_ids, slot_ids,
        ctx_lens, k_scales, v_scales, state_pages, window_pages, window_rows,
        state_slots,
    )


def _prefill_forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_ids: jnp.ndarray,
    block_tables: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    mesh,
    attn_impl: str,
    interpret: bool,
    k_scales,
    v_scales,
    experts_touched: Optional[list],
    state_pages,
    window_pages=None,
    window_rows=None,
    state_slots=None,
) -> tuple[jnp.ndarray, tuple]:
    """The layer loop of ``_prefill_body`` (whose operands these are): it
    reads the pools and writes none. Returns (hidden states [b, s, d], what
    ``_prefill_write`` puts into the pools: the layers' keys ``[L, b, s,
    ...]``, their values (None for a latent pool), the convolution
    layers' states ``[conv layers, b, pages touched, state row]`` and the
    sliding layers' keys and values ``[sliding layers, b, s, ...]``, each
    None where the model has none). A sliding layer (one that has
    ``window``) reads ``window_pages`` through its rows' window tables, whose
    first slot stands for the position ``window_rows`` gives, and every
    layer that attends rotates q and k unless ``_rotates`` says it takes no
    positions. A convolution layer (one that
    has ``conv_in``) takes its state before the chunk from the slot of the
    page that holds the token before ``ctx_lens`` (``page_ids[:, 0]`` where
    the chunk starts inside a page, else the last page of ``block_tables``'
    context; zeros at position 0) and leaves, in one write after the loop,
    the state after the last valid token of every page the chunk touches.
    Scales are None unless the pools are int8 (``KV_QUANT_HBM``), in which
    case the write quantizes and the paged-context gather dequantizes
    chunk-locally — the engine restricts the quantized path to the ``xla``
    single-shard prefill. A linear layer (one that has ``kda_qkv``) takes
    its matrices and carried rows from the slot ``state_slots[:, 0]`` of the
    state pool of slots (``state_pages`` is then ``init_kda_state``'s pair;
    zeros where ``ctx_lens`` is 0), runs the chunked recurrence and leaves
    the state after the row's last valid token for the one write to the slot
    ``state_slots[:, 1]``: a chunk emits no state but its last, so the
    caller cuts chunks where a snapshot is due.

    ``cfg.block_length`` > 1 makes the chunk block-causal (full inside a
    block of that many absolute positions). Callers start the chunk on a
    block boundary: the Pallas kernel reads blocks off chunk indices."""
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    if cfg.block_length > 1 and sp > 1:
        raise ValueError("block_length > 1: the sp ring masks causally")
    latent = cfg.kv_lora_rank > 0
    if latent and (sp > 1 or cfg.block_length > 1 or k_scales is not None):
        raise ValueError("a latent pool: sp, block_length and int8 are not run")
    inv_freq = jnp.asarray(rope_frequencies(
        cfg.qk_rope_head_dim if latent else cfg.hd, cfg.rope_theta,
        cfg.rope_scaling,
    ))
    h = _embed(params, cfg, tokens)  # [b, s, d]
    has_conv = any("conv_in" in layer for layer in params["layers"])
    has_kda = any("kda_qkv" in layer for layer in params["layers"])
    if attn_impl == "pallas" or latent or has_conv or has_kda:
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    if has_kda:
        if (state_pages is None or state_slots is None
                or sp > 1 or k_scales is not None or cfg.block_length > 1):
            raise ValueError(
                "linear layers: the state pool of slots and the rows' slots "
                "are needed; sp, int8 and block_length are not run"
            )
        from ..ops.kda import kda_chunked

        with _scope("kda"):
            kda_s0 = _slot_rows(state_pages[0], state_slots[:, 0], ctx_lens > 0)
            kda_rows0 = _slot_rows(
                state_pages[1], state_slots[:, 0], ctx_lens > 0
            )
        fresh_ks, fresh_krows = [], []
    if has_conv:
        if state_pages is None or sp > 1 or k_scales is not None:
            raise ValueError(
                "convolution layers: the state pool is needed; sp and int8 "
                "are not run"
            )
        page_size = k_pages.shape[2]
        before = jnp.zeros_like(ctx_lens)
        if block_tables.shape[1]:
            before = jnp.take_along_axis(
                block_tables,
                jnp.clip(
                    ctx_lens // page_size - 1, 0, block_tables.shape[1] - 1
                )[:, None],
                axis=1,
            )[:, 0]
        states = _conv_prev_state(
            state_pages, cfg,
            jnp.where(ctx_lens % page_size != 0, page_ids[:, 0], before),
            ctx_lens > 0,
        )
        last, _, _ = _conv_state_plan(ctx_lens, n_valid, page_ids, page_size)
        # rows of ``z`` (the state before the chunk, then the chunk) that
        # are the state after chunk index ``last``
        state_rows = (
            last[:, :, None] + 1 + jnp.arange(cfg.conv_L_cache - 1)[None, None]
        )
    head_scale = cfg.hd**-0.5 if cfg.kv_heads_per_row > 1 else None
    if any("window" in layer for layer in params["layers"]):
        if (window_pages is None or window_rows is None or sp > 1
                or k_scales is not None or cfg.block_length > 1):
            raise ValueError(
                "sliding layers: the window pools and the rows' window "
                "tables are needed; sp, int8 and block_length are not run"
            )
        _, window_tables, window_start = window_rows

    fresh_k = []  # per-layer [b, s, n_kv, hd] — written to pages in one go
    fresh_v = []
    fresh_state = []  # per conv layer [b, pages touched, state row]
    fresh_wk = []  # per sliding layer, as ``fresh_k``: the window pools'
    fresh_wv = []
    aside = []  # a double layer's routed sum, from its first FFN to its second
    for layer in _sublayers(params["layers"]):
        sliding = "window" in layer
        # the layer's index in the pools it reads and writes
        li = len(fresh_wk if sliding else fresh_k)
        gates = _preroute(layer, cfg, h)
        with _scope(
            "conv" if "conv_in" in layer
            else "kda" if "kda_qkv" in layer
            else "attn_window" if sliding else "attn"
        ):
            x = rms_norm(h, layer["attn_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            if "conv_in" in layer:
                out, z = _conv_operator(layer, cfg, x, states[len(fresh_state)])
                fresh_state.append(
                    z[jnp.arange(z.shape[0])[:, None, None], state_rows].reshape(
                        *state_rows.shape[:2], -1
                    )
                )
            elif "kda_qkv" in layer:
                lk = len(fresh_ks)
                taps = cfg.kda_conv_kernel
                q, k, v, g, beta, z = _kda_inputs(
                    layer, cfg, x,
                    kda_rows0[lk].reshape(x.shape[0], taps - 1, -1),
                )
                # a slot that holds no token leaves the state as it was
                g = jnp.where(valid[..., None, None], g, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
                o, s_new = kda_chunked(q, k, v, g, beta, kda_s0[lk])
                out = _kda_output(layer, cfg, x, o)
                fresh_ks.append(s_new)
                # the carried rows after the last valid token
                fresh_krows.append(jnp.take_along_axis(
                    z, (n_valid[:, None] + jnp.arange(taps - 1))[:, :, None],
                    axis=1,
                ).reshape(x.shape[0], -1))
            elif latent:
                # One row a token, absorbed: the kernel over the pool in place
                # or (``xla``) its oracle over gathered pages; the rows go to
                # the pool after the loop, through the same flat-row scatter.
                q_n, q_r, k = _mla_project(layer, cfg, x, positions, inv_freq)
                v = None
                attn = _mla_absorbed(
                    layer, cfg, q_n, q_r, k, k_pages, block_tables, ctx_lens,
                    n_valid, layer_index=li, interpret=interpret,
                    kernel=attn_impl == "pallas",
                )
            else:
                q, k, v = _qkv(layer, cfg, x)
                if _rotates(layer, cfg):
                    q = apply_rope(q, positions, inv_freq)
                    k = apply_rope(k, positions, inv_freq)

                if sliding:
                    # the window pools through the row's window table, whose
                    # first slot is position ``window_start``; ``xla`` is the
                    # kernel's oracle, window included
                    seen = dict(
                        window=cfg.sliding_window, table_start=window_start
                    )
                    if attn_impl == "pallas":
                        attn = _flash_prefill_tp(
                            q, k, v, *window_pages, window_tables, ctx_lens,
                            n_valid, layer=li, interpret=interpret, mesh=mesh,
                            **seen,
                        )
                    else:
                        attn = prefill_with_paged_context(
                            q, k, v, window_pages[0][li], window_pages[1][li],
                            window_tables, ctx_lens, positions=positions,
                            valid=valid, **seen,
                        )
                elif sp > 1:
                    # Sequence-parallel chunk: ring attention over the sp axis,
                    # merged exactly with the paged context (see
                    # _sp_prefill_attention). Takes precedence over attn_impl —
                    # the ring is the sharded equivalent of the xla flash scan.
                    attn = _sp_prefill_attention(
                        q, k, v, k_pages[li], v_pages[li], block_tables, ctx_lens,
                        positions, valid, mesh,
                    )
                elif attn_impl == "pallas":
                    # Flash kernel (ops/flash_prefill.py), which reads the whole
                    # pools' pages where they lie. Engine contract: consecutive
                    # chunk positions, right-padded valid mask.
                    q, k, v = _pack_heads(cfg, q, k, v)
                    attn = _unpack_heads(cfg, _flash_prefill_tp(
                        q, k, v, k_pages, v_pages, block_tables, ctx_lens,
                        n_valid, layer=li, interpret=interpret, mesh=mesh,
                        block_length=cfg.block_length, scale=head_scale,
                    ))
                else:
                    attn = prefill_with_paged_context(
                        q, k, v, _head_pool(cfg, k_pages[li]),
                        _head_pool(cfg, v_pages[li]), block_tables, ctx_lens,
                        positions=positions, valid=valid,
                        k_scales=None if k_scales is None else k_scales[li],
                        v_scales=None if v_scales is None else v_scales[li],
                        block_length=cfg.block_length,
                    )
            if "conv_in" not in layer and "kda_qkv" not in layer:
                b, s, _, _ = attn.shape
                out = _attn_gate(layer, x, attn.reshape(b, s, -1)) @ _w(
                    layer["wo"], h.dtype
                )
                (fresh_wk if sliding else fresh_k).append(k)
                (fresh_wv if sliding else fresh_v).append(v)
            h = h + _post_norm(layer, cfg, "attn_post_norm", out)
        h = _ffn(
            layer, cfg, h, mesh=mesh, interpret=interpret,
            touched=experts_touched, valid=valid, aside=aside, gates=gates,
        )

    fresh = (
        jnp.stack(fresh_k) if fresh_k else None,
        jnp.stack(fresh_v) if fresh_k and not latent else None,
        jnp.stack(fresh_state) if fresh_state else None,
    )
    if has_kda:
        # (the matrices, the carried rows), every linear layer's
        fresh = fresh[:2] + ((jnp.stack(fresh_ks), jnp.stack(fresh_krows)),)
    if fresh_wk:
        fresh += (jnp.stack(fresh_wk), jnp.stack(fresh_wv))
    return h, fresh


def _prefill_write(
    fresh: tuple,  # ``_prefill_forward``'s
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_ids: jnp.ndarray,
    slot_ids: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    k_scales,
    v_scales,
    state_pages,
    window_pages=None,
    window_rows=None,
    state_slots=None,
) -> tuple[jnp.ndarray, jnp.ndarray, Any, Any, Any, Any]:
    """What a chunk leaves in the (donated) pools, each in one scatter over
    all its layers: (k_pages, v_pages, k_scales, v_scales, state_pages,
    window_pages). A pool ``fresh`` has nothing for (a latent model's second,
    a tree of convolution layers alone) passes through untouched, as do the
    scales unless the pools are int8. The sliding layers' keys and values go
    to the window pools at their rows' own page ids (``window_rows``) and the
    slots every pool shares; a token of a block the sequence keeps no window
    page for carries the reserved page 0, which nothing reads."""
    fresh_k, fresh_v, fresh_state, *fresh_window = fresh
    if fresh_window:
        window_pages = tuple(
            _scatter_kv_pages_all_layers(
                pool, new.astype(pool.dtype), window_rows[0], slot_ids, valid
            )
            for pool, new in zip(window_pages, fresh_window)
        )
    if state_slots is not None:
        # linear layers (the pool of slots: matrices and carried rows, and
        # ``fresh_state`` the same pair): every layer's to the rows' write
        # slots, where the row holds a token
        held = jnp.any(valid, axis=1)
        state_pages = tuple(
            _scatter_slots(pool, new, state_slots[:, 1], held)
            for pool, new in zip(state_pages, fresh_state)
        )
    elif fresh_state is not None:
        _, state_page, state_ok = _conv_state_plan(
            ctx_lens, jnp.sum(valid.astype(jnp.int32), axis=1), page_ids,
            k_pages.shape[2],
        )
        state_pages = _scatter_state_pages(
            state_pages, fresh_state, state_page, state_ok
        )
    # One batched scatter over all layers into the donated pools. In-chunk
    # attention never reads these pages (fresh K/V ride function arguments),
    # so deferring the writes is exact — and a single aliased update avoids
    # the full pool copy a per-layer rebuild costs.
    if k_scales is not None:
        k_pages, k_scales = _quantized_scatter_kv_all_layers(
            k_pages, k_scales, fresh_k, page_ids, slot_ids, valid, positions,
        )
        v_pages, v_scales = _quantized_scatter_kv_all_layers(
            v_pages, v_scales, fresh_v, page_ids, slot_ids, valid, positions,
        )
        return k_pages, v_pages, k_scales, v_scales, state_pages, window_pages
    if fresh_k is not None:
        k_pages = _scatter_kv_pages_all_layers(
            k_pages, fresh_k.astype(k_pages.dtype), page_ids, slot_ids, valid
        )
    if fresh_v is not None:
        v_pages = _scatter_kv_pages_all_layers(
            v_pages, fresh_v.astype(v_pages.dtype), page_ids, slot_ids, valid
        )
    return k_pages, v_pages, k_scales, v_scales, state_pages, window_pages


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "attn_impl", "return_all_logits", "interpret",
    ),
    donate_argnames=(
        "k_pages", "v_pages", "k_scales", "v_scales", "state_pages",
        "window_pages",
    ),
)
def prefill(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, s] int32, right-padded
    positions: jnp.ndarray,  # [b, s] int32 absolute positions (pad value free)
    valid: jnp.ndarray,  # [b, s] bool — False positions are fully masked
    k_pages: jnp.ndarray,  # [n_layers, pages, page_size, n_kv, hd]
    v_pages: jnp.ndarray,
    page_ids: jnp.ndarray,  # [b, s] destination page per token
    slot_ids: jnp.ndarray,  # [b, s] destination slot per token
    block_tables: jnp.ndarray,  # [b, max_ctx_pages] int32 — cached-context pages
    ctx_lens: jnp.ndarray,  # [b] int32 — prefix-cached context length (0 = fresh)
    mesh=None,  # tp mesh for expert-parallel MoE dispatch
    attn_impl: str = "xla",  # "xla" (scan flash) | "pallas" (flash kernel)
    return_all_logits: bool = False,  # [b, s, vocab] for spec-decode verify
    k_scales=None,  # [L, P, n_kv] f32 — int8 pools (KV_QUANT_HBM)
    v_scales=None,
    interpret: bool = False,  # Pallas kernels interpreted (CPU tests)
    *,
    state_pages=None,  # ``init_state_pages``: a model with conv layers
    window_pages=None,  # ``init_window_pages``: a model with sliding layers
    window_rows=None,  # its rows' (page_ids [b, s], tables [b, w], starts [b])
    state_slots=None,  # [b, 2] int32: a model with linear layers (below)
) -> tuple[jnp.ndarray, ...]:
    """Process a prompt chunk: returns (logits at last valid position per
    sequence [b, vocab], updated k_pages, v_pages), then the updated scale
    pools where the pools are int8, then the updated ``state_pages`` where
    one was given (a model with convolution layers; absent, in arguments
    and results, for every other), then the updated ``window_pages`` pair
    where one was given (a model with sliding layers; absent for every
    other). ``window_rows``: where each token's keys and values go in the
    window pools (the slots are ``slot_ids``), the window table of each row
    (the window pages that hold its context from position ``starts[i]`` up
    to ``ctx_lens[i]``) and that position. ``state_slots``: for a model with
    linear layers (``state_pages`` is ``init_kda_state``'s pair) the slot
    each row's state is read from and the slot its state after the chunk is
    written to, ``[read, write]`` a row: the same slot for a sequence that
    goes on, two where it starts from a snapshot, which is left as it was.

    The chunk attends causally within itself AND to ``ctx_lens`` tokens of
    prefix-cached context already resident in the page pool — this is how a
    prefix-cache hit skips recomputing the shared prefix. Fresh sequences
    pass ``ctx_lens = 0``.

    Mask contract: ``valid`` must be a RIGHT-PADDED prefix mask — per row,
    ``valid[i] == (arange(s) < n_valid[i])``. The ``xla`` path honors an
    arbitrary mask exactly (for a latent model it does not: both of its
    paths take the count), but the ``pallas`` kernel collapses it to a
    per-sequence count, so a mask with interior holes silently computes
    wrong attention on ``attn_impl="pallas"``. The engine always satisfies
    this; non-engine callers can set ``LLMD_CHECK_PREFILL_MASK=1`` to
    verify at runtime (host-callback assert; small sync cost — debug only).
    The flag is read at jit TRACE time: set it before the first prefill
    call of a given shape (or call ``prefill.clear_cache()``) — flipping it
    after a shape is compiled has no effect on that cached trace.
    """
    _check_prefill(mesh, attn_impl, valid, k_scales, v_scales)
    h, *pools = _prefill_body(
        params, cfg, tokens, positions, valid, k_pages, v_pages,
        page_ids, slot_ids, block_tables, ctx_lens, mesh, attn_impl,
        interpret, k_scales, v_scales, state_pages=state_pages,
        window_pages=window_pages, window_rows=window_rows,
        state_slots=state_slots,
    )
    if return_all_logits:
        # Every chunk position's next-token logits [b, s, vocab] — the
        # speculative-decode verify step scores all k+1 proposed tokens in
        # this one dispatch (chunks there are tiny, so the full-position
        # lm_head stays cheap).
        logits = _logits(params, cfg, h)
    else:
        logits = _last_logits(params, cfg, h, valid)
    return (logits,) + _prefill_results(
        pools, k_scales, state_pages, window_pages
    )


def _check_prefill(mesh, attn_impl: str, valid, k_scales, v_scales) -> None:
    """What ``prefill`` and ``prefill_packed`` refuse, and the mask check
    that ``LLMD_CHECK_PREFILL_MASK`` asks for (see ``prefill``)."""
    if attn_impl not in ("xla", "pallas"):
        raise ValueError(f"unknown attn_impl {attn_impl!r}")
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    if sp > 1 and valid.shape[1] % sp != 0:
        raise ValueError(
            f"chunk length {valid.shape[1]} not divisible by sp={sp}"
        )
    if attn_impl == "pallas" and os.environ.get("LLMD_CHECK_PREFILL_MASK"):
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
        contract = jnp.arange(valid.shape[1])[None, :] < n_valid[:, None]
        jax.debug.callback(
            _check_right_padded_mask, jnp.all(contract == valid)
        )
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    if k_scales is not None and (sp > 1 or attn_impl == "pallas"):
        raise ValueError(
            "KV_QUANT_HBM prefill requires the xla single-shard path"
        )


def _last_logits(params: Params, cfg: LlamaConfig, h, valid) -> jnp.ndarray:
    """Logits [b, vocab] at each sequence's last valid position."""
    last_idx = jnp.maximum(jnp.sum(valid.astype(jnp.int32), axis=1) - 1, 0)  # [b]
    h_last = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)[:, 0]  # [b, d]
    return _logits(params, cfg, h_last[:, None, :])[:, 0]


def _prefill_results(pools, k_scales, state_pages, window_pages=None) -> tuple:
    """``_prefill_write``'s six as ``prefill`` returns them: knob-off
    callers keep the legacy (k_pages, v_pages); quantized callers get the
    updated scale pools appended, a model with state its state pool, a
    model with sliding layers its pair of window pools (``k_scales`` /
    ``state_pages`` / ``window_pages``: the caller's own, None without)."""
    k_pages, v_pages, new_k_scales, new_v_scales, new_state, new_window = pools
    out = (k_pages, v_pages)
    if k_scales is not None:
        out += (new_k_scales, new_v_scales)
    if state_pages is not None:
        out += (new_state,)
    if window_pages is not None:
        out += (new_window,)
    return out


def pack_window_rows(page_ids, tables, starts) -> np.ndarray:
    """A prefill dispatch's ``window_rows`` as ONE host array, int32 ``[b,
    chunk + window table + 1]`` = ``[page ids | window table | start]``
    (``prefill_packed`` slices it apart by its ``chunk``); a decode
    dispatch's window tables and starts likewise, with no page ids
    (``decode_steps``: ``[window table | start]``)."""
    parts = [tables, np.asarray(starts)[:, None]]
    if page_ids is not None:
        parts.insert(0, page_ids)
    return np.concatenate(parts, axis=1, dtype=np.int32)


def pack_prefill_inputs(
    tokens, positions, valid, page_ids, slot_ids, block_tables, ctx_lens
) -> np.ndarray:
    """``prefill``'s seven host arrays as ONE, so that a dispatch costs one
    upload: int32 ``[b, 5 * chunk + ctx_pages + 1]`` = ``[tokens | positions
    | valid | page_ids | slot_ids | block_tables | ctx_len]``, a width the
    two buckets that key the program fix. ``prefill_packed`` takes it."""
    return np.concatenate(
        [tokens, positions, valid, page_ids, slot_ids, block_tables,
         np.asarray(ctx_lens)[:, None]],
        axis=1, dtype=np.int32,
    )


@functools.partial(
    jax.jit, static_argnames=("cfg", "mesh", "attn_impl", "interpret")
)
def _prefill_rows(
    params: Params,
    cfg: LlamaConfig,
    rows: tuple,  # (tokens, positions, valid, page_ids, block_tables,
    # ctx_lens, window_rows: None for a model without sliding layers,
    # state_slots: None for a model without linear layers)
    k_pages,
    v_pages,
    k_scales,
    v_scales,
    state_pages,
    window_pages=None,
    *,
    mesh,
    attn_impl: str,
    interpret: bool,
) -> tuple[jnp.ndarray, tuple]:
    """``prefill_packed``'s forward over some rows of its operand: (their
    last-position logits ``[1, rows, vocab]``, ``_prefill_forward``'s fresh
    keys, values and states), every array with its rows on the SECOND axis.
    It reads the pools and writes none. A jit of its own so that
    ``prefill_packed`` traces it ONCE for the shapes of its results and for
    its loop's body."""
    (tokens, positions, valid, page_ids, block_tables, ctx_lens, window_rows,
     state_slots) = rows
    h, fresh = _prefill_forward(
        params, cfg, tokens, positions, valid, k_pages, v_pages, page_ids,
        block_tables, ctx_lens, mesh, attn_impl, interpret, k_scales,
        v_scales, None, state_pages, window_pages, window_rows, state_slots,
    )
    return _last_logits(params, cfg, h, valid)[None], fresh


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "mesh", "attn_impl", "interpret"),
    donate_argnames=(
        "k_pages", "v_pages", "k_scales", "v_scales", "state_pages",
        "window_pages",
    ),
)
def prefill_packed(
    params: Params,
    cfg: LlamaConfig,
    packed: jnp.ndarray,  # [b, 5 * chunk + ctx_pages + 1]: ``pack_prefill_inputs``
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    *,
    chunk: int,  # the chunk bucket: where ``packed``'s columns divide
    mesh=None,
    attn_impl: str = "xla",
    k_scales=None,
    v_scales=None,
    interpret: bool = False,
    state_pages=None,
    window_pages=None,
    window_packed=None,  # [b, chunk + window table + 1]: ``pack_window_rows``
    state_slots=None,  # [b, 2] int32: ``prefill``'s, a model with linear layers
) -> tuple[jnp.ndarray, ...]:
    """``prefill`` as the engine dispatches it: the same forward, logits
    and write behind one packed operand that is sliced apart here. ``chunk``
    and the operand's width are the two buckets that key ``prefill``, so the
    set of programs is the same. A model with sliding layers brings its
    ``window_rows`` as a second packed operand (``[page ids | window table |
    start]``), and no other model has it.

    The program holds the forward (``_prefill_forward``, last-position
    logits) for ONE row of the bucketed width, inside a loop that runs as
    many times as the rows reach that hold a sequence, which it reads from
    ``packed`` on the device (the engine fills rows from 0): a dispatch of
    one sequence computes one row, not the operand's ``b``, and the program
    is no larger than the one body of ``b`` rows was (a program that holds a
    body a row count loads as many times slower from the compile cache:
    ``PERF.md`` section 6, PR 42). Each turn reads the weights again, which
    a row of a few hundred tokens pays for and a full operand of short rows
    would not. A turn READS the pools and leaves its row's logits, keys,
    values and states in arrays of ``b`` rows (zero in the rows no turn
    computed); the one write of the donated pools comes after the loop,
    over ``b`` rows with their invalid slots masked as ever. Under a mesh
    the program is the one body of ``b`` rows."""
    b = packed.shape[0]
    tokens, positions, valid, page_ids, slot_ids = (
        packed[:, i * chunk : (i + 1) * chunk] for i in range(5)
    )
    valid, ctx_lens = valid != 0, packed[:, -1]
    _check_prefill(mesh, attn_impl, valid, k_scales, v_scales)
    window_rows = None
    if window_packed is not None:
        window_rows = (
            window_packed[:, :chunk], window_packed[:, chunk:-1],
            window_packed[:, -1],
        )
    rows = (
        tokens, positions, valid, page_ids, packed[:, 5 * chunk : -1],
        ctx_lens, window_rows, state_slots,
    )
    forward = functools.partial(
        _prefill_rows, params, cfg, k_pages=k_pages, v_pages=v_pages,
        k_scales=k_scales, v_scales=v_scales, state_pages=state_pages,
        window_pages=window_pages,
        mesh=mesh, attn_impl=attn_impl, interpret=interpret,
    )
    if mesh is not None or b == 1:
        logits, fresh = forward(rows)
    else:
        def row(i):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1), rows
            )

        def turn(i, out):
            return jax.tree.map(
                lambda whole, one: jax.lax.dynamic_update_slice_in_dim(
                    whole, one, i, 1),
                out, forward(row(i)),
            )

        held = jnp.any(valid, axis=1)
        logits, fresh = jax.lax.fori_loop(
            0, jnp.max(jnp.where(held, jnp.arange(1, b + 1), 0)), turn,
            jax.tree.map(
                lambda one: jnp.zeros(
                    (one.shape[0], b) + one.shape[2:], one.dtype),
                jax.eval_shape(forward, row(0)),
            ),
        )
    pools = _prefill_write(
        fresh, positions, valid, k_pages, v_pages, page_ids, slot_ids,
        ctx_lens, k_scales, v_scales, state_pages, window_pages, window_rows,
        state_slots,
    )
    return (logits[0],) + _prefill_results(
        pools, k_scales, state_pages, window_pages
    )


def _decode_body(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b] int32 — last sampled token per sequence
    positions: jnp.ndarray,  # [b] int32 — position of this token
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [b, max_pages] int32
    seq_lens: jnp.ndarray,  # [b] int32 — context length INCLUDING this token
    page_size: int,
    interpret: bool,
    mesh=None,
    k_scales=None,  # [L, P, n_kv] f32 when KV_QUANT_HBM=int8
    v_scales=None,
    state_pages=None,  # ``init_state_pages``: a model with conv layers
    experts_touched: Optional[list] = None,  # see ``_moe_mlp_routed``
    window_pages=None,  # ``init_window_pages``: a model with sliding layers
    window_tables=None,  # [b, window pages] int32: its lanes' window tables
    window_start=None,  # [b] int32: the position of each table's first slot
    state_slots=None,  # [b, 3] int32: a model with linear layers (below)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Any, Any, Any, Any]:
    """Single decode step (traced body shared by ``decode_step`` and the
    fused ``decode_steps`` scan). Writes this token's K/V into its page
    slot, runs paged attention over the full context, returns
    (logits [b, vocab], k_pages, v_pages, k_scales, v_scales, state_pages,
    window_pages): ``window_pages`` is a None pass-through unless the model
    has sliding layers, each of which attends over its lane's window table
    alone (the kernel's grid is as wide as that table, whatever the
    context) and writes this token's K/V into the window pools
    — scales are None pass-throughs unless the pools are int8
    (``KV_QUANT_HBM``), ``state_pages`` unless the model has convolution
    layers: such a layer reads the slot of the page that holds the token
    before this one (through the block table: the page before, at a page's
    first slot) and writes the new state to this token's page's slot, so a
    lane that crosses a page boundary inside a burst leaves the finished
    page's snapshot behind on the device. A model with linear layers
    (``state_pages`` is ``init_kda_state``'s pair) brings ``state_slots``,
    ``[slot a, slot b, switch]`` a lane: the token at a position before
    ``switch`` reads and writes slot ``a``, the one AT ``switch`` reads ``a``
    and writes ``b``, later ones read and write ``b`` — so a lane whose
    burst passes a snapshot boundary leaves the state at the boundary behind
    in ``a`` and goes on in ``b``, with no copy (``a == b``: one slot)."""
    latent = cfg.kv_lora_rank > 0
    if latent and (mesh is not None or k_scales is not None):
        raise ValueError("a latent pool: tp, sp and int8 are not run")
    inv_freq = jnp.asarray(rope_frequencies(
        cfg.qk_rope_head_dim if latent else cfg.hd, cfg.rope_theta,
        cfg.rope_scaling,
    ))
    b = tokens.shape[0]
    h = _embed(params, cfg, tokens)[:, None, :]  # [b, 1, d]

    # This token's page/slot from its position.
    page_of_pos = positions // page_size  # index into block table
    my_page = jnp.take_along_axis(block_tables, page_of_pos[:, None], axis=1)[:, 0]
    my_slot = positions % page_size
    valid = jnp.ones((b, 1), bool)
    has_conv = any("conv_in" in layer for layer in params["layers"])
    if has_conv:
        if state_pages is None or mesh is not None or k_scales is not None:
            raise ValueError(
                "convolution layers: the state pool is needed; tp, sp and "
                "int8 are not run"
            )
        states = _conv_prev_state(
            state_pages, cfg,
            jnp.take_along_axis(
                block_tables,
                (jnp.maximum(positions - 1, 0) // page_size)[:, None], axis=1,
            )[:, 0],
            positions > 0,
        )
    has_kda = any("kda_qkv" in layer for layer in params["layers"])
    if has_kda:
        if (state_pages is None or state_slots is None
                or mesh is not None or k_scales is not None):
            raise ValueError(
                "linear layers: the state pool of slots and the lanes' slots "
                "are needed; tp, sp and int8 are not run"
            )
        from ..ops.kda import kda_decode

        kda_pool, kda_rows_pool = state_pages
        slot_a, slot_b, switch = (state_slots[:, i] for i in range(3))
        kda_read = jnp.where(positions <= switch, slot_a, slot_b)
        kda_write = jnp.where(positions < switch, slot_a, slot_b)
        kda_fresh = jnp.zeros_like(positions)  # position 0 is a prefill's
        with _scope("kda"):
            kda_rows0 = _slot_rows(kda_rows_pool, kda_read)
        fresh_krows = []
    head_scale = cfg.hd**-0.5 if cfg.kv_heads_per_row > 1 else None
    if any("window" in layer for layer in params["layers"]):
        if (window_pages is None or window_tables is None
                or mesh is not None or k_scales is not None):
            raise ValueError(
                "sliding layers: the window pools and the lanes' window "
                "tables are needed; tp, sp and int8 are not run"
            )
        my_window_page = jnp.take_along_axis(
            window_tables,
            jnp.maximum(positions - window_start, 0)[:, None] // page_size,
            axis=1,
        )[:, 0]

    fresh_k = []  # per-layer [b, 1, n_kv, hd]; written to pages in one go
    fresh_v = []
    fresh_state = []  # per conv layer [b, 1, state row]
    fresh_wk = []  # per sliding layer, as ``fresh_k``: the window pools'
    fresh_wv = []
    aside = []  # a double layer's routed sum, from its first FFN to its second
    for layer in _sublayers(params["layers"]):
        sliding = "window" in layer
        # the layer's index in the pools it reads and writes
        li = len(fresh_wk if sliding else fresh_k)
        gates = _preroute(layer, cfg, h)
        with _scope(
            "conv" if "conv_in" in layer
            else "kda" if "kda_qkv" in layer
            else "attn_window" if sliding else "attn"
        ):
            x = rms_norm(h, layer["attn_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            if "conv_in" in layer:
                out, z = _conv_operator(layer, cfg, x, states[len(fresh_state)])
                fresh_state.append(z[:, 1:].reshape(b, 1, -1))
            elif "kda_qkv" in layer:
                # one step of every lane's state where it lies in the pool
                # (kernel ``kda_decode``); the carried rows go to the pool
                # after the loop, every layer's in one scatter of whole slots
                # (``kda_conv_tile``)
                lk = len(fresh_krows)
                q, k, v, g, beta, z = _kda_inputs(
                    layer, cfg, x,
                    kda_rows0[lk].reshape(b, cfg.kda_conv_kernel - 1, -1),
                )
                o, kda_pool = kda_decode(
                    kda_pool, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    kda_read, kda_write, kda_fresh, lk, interpret=interpret,
                )
                out = _kda_output(layer, cfg, x, o[:, None])
                fresh_krows.append(z[:, 1:].reshape(b, -1))
            elif latent:
                # Absorbed decode (kernel ``mla_decode``): every head reads the
                # lane's latent rows where they lie, once, as key and as value;
                # the token's own row rides as an argument and is written after
                # the loop like every other model's.
                q_n, q_r, k = _mla_project(
                    layer, cfg, x, positions[:, None], inv_freq
                )
                v = None
                attn = _mla_absorbed(
                    layer, cfg, q_n, q_r, k, k_pages, block_tables,
                    jnp.maximum(seq_lens - 1, 0), (seq_lens > 0).astype(jnp.int32),
                    layer_index=li, interpret=interpret,
                )  # [b, 1, H, d_v]
            else:
                q, k, v = _qkv(layer, cfg, x)
                if _rotates(layer, cfg):
                    q = apply_rope(q, positions[:, None], inv_freq)
                    k = apply_rope(k, positions[:, None], inv_freq)
                q, k, v = _pack_heads(cfg, q, k, v)

                # The kernel takes the current token's K/V as arguments (pages hold
                # only history), so the pool write happens ONCE for all layers after
                # the loop — a single aliased scatter instead of a per-layer pool
                # rebuild (which cost 2×pool bytes of HBM traffic per token). The
                # kernel reads the pool in the default layout; that the write does
                # too (it did not until PR 29: see _scatter_kv_pages_all_layers) is
                # what tests/test_pool_layout.py holds on the compiled program.
                # A sliding layer reads its own pools through its lane's
                # window table, whose first slot is ``window_start``.
                if sliding:
                    pools = (*window_pages, window_tables)
                    seen = dict(window=cfg.sliding_window,
                                table_start=window_start)
                else:
                    pools = (k_pages, v_pages, block_tables)
                    seen = dict(k_scale=k_scales, v_scale=v_scales)
                attn = _unpack_heads(cfg, _paged_attention_tp(
                    q[:, 0],  # [b, n_heads, hd]
                    *pools,  # FULL [L, P, ps, n_kv, hd] pools; layer a word
                    seq_lens,
                    k[:, 0],  # [b, n_kv, hd]
                    v[:, 0],
                    interpret=interpret,
                    mesh=mesh,
                    layer=li,
                    scale=head_scale,
                    **seen,
                ))  # [b, n_heads, hd]
            if "conv_in" not in layer and "kda_qkv" not in layer:
                out = (
                    _attn_gate(layer, x[:, 0], attn.reshape(b, -1))
                    @ _w(layer["wo"], h.dtype)
                )[:, None, :]
                (fresh_wk if sliding else fresh_k).append(k)
                (fresh_wv if sliding else fresh_v).append(v)
            h = h + _post_norm(layer, cfg, "attn_post_norm", out)
        h = _ffn(
            layer, cfg, h, mesh=mesh, interpret=interpret,
            touched=experts_touched, aside=aside, gates=gates,
        )

    if fresh_wk:
        window_pages = tuple(
            _scatter_kv_pages_all_layers(
                pool, jnp.stack(new).astype(pool.dtype),
                my_window_page[:, None], my_slot[:, None], valid,
            )
            for pool, new in zip(window_pages, (fresh_wk, fresh_wv))
        )
    if fresh_state:
        state_pages = _scatter_state_pages(
            state_pages, jnp.stack(fresh_state), my_page[:, None], valid
        )
    if has_kda:
        state_pages = (kda_pool, _scatter_slots(
            kda_rows_pool, jnp.stack(fresh_krows), kda_write, valid[:, 0]
        ))
    if not fresh_k:  # a tree of convolution layers alone: no key or value
        return (
            _logits(params, cfg, h)[:, 0], k_pages, v_pages, k_scales,
            v_scales, state_pages, window_pages,
        )
    if k_scales is not None:
        k_pages, k_scales = _quantized_scatter_kv_all_layers(
            k_pages, k_scales, jnp.stack(fresh_k),
            my_page[:, None], my_slot[:, None], valid, positions[:, None],
        )
        v_pages, v_scales = _quantized_scatter_kv_all_layers(
            v_pages, v_scales, jnp.stack(fresh_v),
            my_page[:, None], my_slot[:, None], valid, positions[:, None],
        )
    else:
        k_pages = _scatter_kv_pages_all_layers(
            k_pages, jnp.stack(fresh_k).astype(k_pages.dtype),
            my_page[:, None], my_slot[:, None], valid,
        )
        if not latent:
            v_pages = _scatter_kv_pages_all_layers(
                v_pages, jnp.stack(fresh_v).astype(v_pages.dtype),
                my_page[:, None], my_slot[:, None], valid,
            )
    return (
        _logits(params, cfg, h)[:, 0],
        k_pages,
        v_pages,
        k_scales,
        v_scales,
        state_pages,
        window_pages,
    )


#: columns behind the block table in ``decode_steps``' packed operand
DECODE_PACKED_TAIL = 5

#: what a burst's leading columns count for a model whose routed layers are
#: told what they hold (``burst_counts`` of them; else the first alone)
BURST_COUNTS_HELD = ("experts_touched", "zero_places", "held_places")


def burst_counts(cfg: LlamaConfig) -> int:
    """Columns of counts before the tokens of a ``decode_steps`` burst: the
    experts read, and for a model with zero experts or a held range of the
    experts also the places that fell on zero experts and on held ones
    (``BURST_COUNTS_HELD``; ``_moe_mlp_routed`` counts all three)."""
    routed = cfg.n_experts and cfg.moe_dispatch == "routed"
    return 1 if not routed or cfg.holds_every_expert else len(BURST_COUNTS_HELD)


def pack_decode_inputs(
    positions,  # [b] int32 — position of each lane's input token
    block_tables,  # [b, max_pages] int32 (covers num_steps growth)
    seq_lens,  # [b] int32 — context length INCLUDING the input token; 0 = idle
    temperature,  # [b] f32; 0 = greedy
    top_k,  # [b] int32; 0 = disabled
    top_p,  # [b] f32; 1 = disabled
) -> np.ndarray:
    """The host side of ``decode_steps``' packed operand: int32
    ``[b, max_pages + DECODE_PACKED_TAIL]`` = ``[block table | position,
    seq_len | top_k, temperature, top_p]`` (``pack_sampling_params``: the
    two floats ride as their bits)."""
    return np.concatenate(
        [
            np.asarray(block_tables, np.int32),
            np.stack([positions, seq_lens], axis=1).astype(np.int32),
            pack_sampling_params(temperature, top_k, top_p),
        ],
        axis=1,
    )


def _unpack_decode_inputs(packed: jnp.ndarray):
    """``pack_decode_inputs`` undone inside the program (slices of one
    operand): block tables, positions, seq_lens, temperature, top_k,
    top_p."""
    table_w = packed.shape[1] - DECODE_PACKED_TAIL
    temperature, top_k, top_p = unpack_sampling_params(packed[:, table_w + 2 :])
    return (
        packed[:, :table_w], packed[:, table_w], packed[:, table_w + 1],
        temperature, top_k, top_p,
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "interpret", "mesh"),
    donate_argnames=(
        "k_pages", "v_pages", "k_scales", "v_scales", "state_pages",
        "window_pages",
    ),
)
def decode_step(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b] int32 — last sampled token per sequence
    positions: jnp.ndarray,  # [b] int32 — position of this token
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [b, max_pages] int32
    seq_lens: jnp.ndarray,  # [b] int32 — context length INCLUDING this token
    *,
    page_size: int,
    interpret: bool = False,
    mesh=None,  # tp mesh for head-parallel decode attention
    k_scales=None,  # [L, P, n_kv] f32 — int8 pools (KV_QUANT_HBM)
    v_scales=None,
    state_pages=None,  # ``init_state_pages``: a model with conv layers
    window_pages=None,  # ``init_window_pages``: a model with sliding layers
    window_tables=None,  # [b, window pages] int32
    window_start=None,  # [b] int32: the position of each table's first slot
    state_slots=None,  # [b, 3] int32: ``_decode_body``'s, linear layers
) -> tuple[jnp.ndarray, ...]:
    """One decode step; sampling stays with the caller (host or jit).
    Returns the legacy 3-tuple, with updated scale pools appended when
    the pools are quantized, the updated state pool when one was given
    (a model with convolution layers) and the updated pair of window pools
    when one was given (a model with sliding layers)."""
    stateful, windowed = state_pages is not None, window_pages is not None
    (
        logits, k_pages, v_pages, k_scales, v_scales, state_pages,
        window_pages,
    ) = _decode_body(
        params, cfg, tokens, positions, k_pages, v_pages,
        block_tables, seq_lens, page_size, interpret, mesh,
        k_scales, v_scales, state_pages, window_pages=window_pages,
        window_tables=window_tables, window_start=window_start,
        state_slots=state_slots,
    )
    extra = () if k_scales is None else (k_scales, v_scales)
    if stateful:
        extra += (state_pages,)
    if windowed:
        extra += (window_pages,)
    return (logits, k_pages, v_pages) + extra


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "num_steps", "interpret", "mesh"),
    donate_argnames=(
        "k_pages", "v_pages", "k_scales", "v_scales", "state_pages",
        "window_pages",
    ),
)
def decode_steps(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b] int32 — last sampled token per sequence,
    # or [b, n]: a burst's own output, whose last column is taken here
    # (the count in its first column is never an id: ``n`` >= 2)
    packed: jnp.ndarray,  # [b, max_pages + 5] int32: ``pack_decode_inputs``
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    rng_key: jax.Array,
    *,
    page_size: int,
    num_steps: int,
    interpret: bool = False,
    mesh=None,  # tp mesh for head-parallel decode attention
    k_scales=None,  # [L, P, n_kv] f32 — int8 pools (KV_QUANT_HBM)
    v_scales=None,
    state_pages=None,  # ``init_state_pages``: a model with conv layers
    window_pages=None,  # ``init_window_pages``: a model with sliding layers
    window_packed=None,  # [b, window pages + 1] int32: ``pack_window_rows``
    state_slots=None,  # [b, 3] int32: ``_decode_body``'s, linear layers
) -> tuple[jnp.ndarray, ...]:
    """``num_steps`` fused decode iterations with on-device sampling.

    The device-resident decode loop: one ``lax.scan`` over single-step
    bodies, sampling each next token on-device, so the host syncs once per
    ``num_steps`` tokens instead of once per token. This is the TPU-native
    answer to per-dispatch host latency (the reference never runs a model;
    its vLLM pods solve this on the GPU side). Returns (``burst [b, 1 +
    num_steps]`` int32, k_pages, v_pages), then the scale pools where the
    pools are int8, then ``state_pages`` where one was given (carried
    through the scan as the pools are), then the pair ``window_pages`` where
    one was given (a model with sliding layers, whose lanes' window tables
    and the positions they start at ride in ``window_packed``, a second
    small operand no other model has; the tables cover the burst's growth as
    ``block_tables`` do). ``burst[:, 1:]`` are the sampled
    tokens (behind ``burst_counts(cfg)`` columns of counts: one for every
    model but one whose routed layers are told what they hold, which has
    ``BURST_COUNTS_HELD``); ``burst[:, 0]`` is, in every lane, the number of distinct
    experts the burst's rows chose, summed over the routed layers and the
    steps (what ``_moe_mlp_routed`` counts on the device: the experts whose
    weights the grouped matmuls read, padded lanes' rows included; 0 for a
    model or a dispatch strategy without that count) — one array, so the
    burst still costs one fetch, as ``denoise_steps`` packs its own. The
    caller must pre-extend ``block_tables`` to cover ``num_steps`` of
    growth; lanes that finish early keep decoding into their reserved pages
    and the host discards the surplus tokens. ``tokens`` may be the ``burst``
    a dispatch returned: the next one then starts from its last column on
    the device, with no program between the two and none beside this one.

    Every other per-lane input arrives as ONE array (``pack_decode_inputs``:
    one upload a dispatch, not six) and is sliced apart here, inside the
    program: the benchmark's step rooflines divide by the mean time of every
    traced module whose name carries ``decode_steps``, so no second program
    of that name may run beside this one. ``tokens`` stays an operand of its
    own: a chained dispatch hands them over where they lie on the device.
    """
    quantized = k_scales is not None
    stateful, windowed = state_pages is not None, window_pages is not None
    # the small operand a model's second pool needs (a model has one such pool)
    window = {}
    if windowed:
        window = dict(
            window_tables=window_packed[:, :-1], window_start=window_packed[:, -1]
        )
    elif state_slots is not None:
        window = dict(state_slots=state_slots)
    if tokens.ndim == 2:
        tokens = tokens[:, -1]
    block_tables, positions, seq_lens, temperature, top_k, top_p = (
        _unpack_decode_inputs(packed)
    )
    # The sampler's gate: the lanes' parameters do not change inside the
    # burst, so whether any lane samples is decided once, out here.
    any_sampled = jnp.any(temperature > 0)

    def body(carry, key):
        tokens, positions, seq_lens, k_pages, v_pages, k_sc, v_sc, st, wp = carry
        touched = []
        logits, k_pages, v_pages, k_sc, v_sc, st, wp = _decode_body(
            params, cfg, tokens, positions, k_pages, v_pages,
            block_tables, seq_lens, page_size, interpret, mesh,
            k_sc, v_sc, st, experts_touched=touched, window_pages=wp,
            **window,
        )
        nxt = sample_tokens(
            logits.astype(jnp.float32), temperature, top_k, top_p, key,
            any_sampled,
        )
        return (
            nxt, positions + 1, seq_lens + 1, k_pages, v_pages, k_sc, v_sc,
            st, wp,
        ), (nxt, sum(touched, jnp.zeros((), jnp.int32)))

    # None scales (and a None state pool, and None window pools) are valid
    # (empty) scan-carry leaves, so the knob-off trace is unchanged apart
    # from the tuple arity.
    carry0 = (
        tokens, positions, seq_lens, k_pages, v_pages, k_scales, v_scales,
        state_pages, window_pages,
    )
    keys = jax.random.split(rng_key, num_steps)
    if num_steps == 1:
        # The step-per-token loop lands here every iteration: skip the
        # scan machinery for a plain body call. Consumes keys[0] exactly
        # like the scan's first slice, so sampled streams are
        # bit-identical across paths.
        (
            _, _, _, k_pages, v_pages, k_scales, v_scales, state_pages,
            window_pages,
        ), (nxt, n_touched) = body(carry0, keys[0])
        toks = nxt[:, None]
    else:
        (
            _, _, _, k_pages, v_pages, k_scales, v_scales, state_pages,
            window_pages,
        ), (toks, n_touched) = jax.lax.scan(body, carry0, keys)
        toks, n_touched = toks.T, jnp.sum(n_touched, axis=0)
    # one column, or ``BURST_COUNTS_HELD`` of them (``burst_counts``)
    n_touched = n_touched.reshape(1, -1)
    burst = jnp.concatenate(
        [jnp.broadcast_to(n_touched, (toks.shape[0], n_touched.shape[1])),
         toks], axis=1,
    )
    extra = (k_scales, v_scales) if quantized else ()
    if stateful:
        extra += (state_pages,)
    if windowed:
        extra += (window_pages,)
    return (burst, k_pages, v_pages) + extra


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "page_size", "num_rounds", "s_chunk", "ngram", "spec_k",
        "max_scan", "table_w", "mesh", "attn_impl", "interpret",
    ),
    donate_argnames=("k_pages", "v_pages"),
)
def spec_decode_steps(
    params: Params,
    cfg: LlamaConfig,
    packed_i32: jnp.ndarray,  # [b, W + table_w + 5] int32 — see below
    fparams: jnp.ndarray,  # [b, 2] f32 — (temperature, top_p) per lane
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    rng_key: jax.Array,
    *,
    page_size: int,
    num_rounds: int,
    s_chunk: int,  # verify chunk width (>= spec_k + 1, lane/sp aligned)
    ngram: int,
    spec_k: int,
    max_scan: int,
    table_w: int,  # block-table width inside packed_i32
    mesh=None,
    attn_impl: str = "xla",
    interpret: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """``num_rounds`` fused speculative-decode rounds with ON-DEVICE
    prompt-lookup proposals — one host sync per burst instead of one per
    verify dispatch (the spec-side analogue of ``decode_steps``; composes
    speculation with the pipelined-burst idea by chaining rounds through
    device state rather than the host).

    Each round, per lane: (1) PROPOSE — find the latest earlier occurrence
    of the window's final ``ngram`` and take up to ``spec_k`` followers,
    clamped by the remaining token budget and the host's adaptive gate
    (identical semantics to the host-side ``_propose_prompt_lookup``);
    (2) VERIFY — one prefill-style forward over
    ``[last committed token ++ drafts]`` against the paged context
    (``_prefill_body``), full-position logits; (3) ACCEPT — greedy lanes
    take the longest draft prefix matching argmax plus the correction,
    temperature>0 lanes run deterministic-draft speculative sampling
    (``ops/sampling.spec_sample``); (4) COMMIT ON DEVICE — append the
    emitted tokens to the window and advance ``seq_lens``/``budgets``, so
    the next round proposes from the updated context with no host
    round-trip. Rejected drafts leave stale KV beyond ``seq_lens`` in
    pages the sequence owns; the next round's chunk rewrite of the
    corrected position and the host's budget-bounded commits make that
    pure bookkeeping (same argument as the fused-burst surplus tokens).

    The caller sizes ``window`` so it cannot overflow
    (``W >= max wlen + num_rounds * (spec_k + 1)``) and pre-reserves pages
    for the worst-case growth. A lane whose budget hits 0 keeps verifying
    its last position (emitting nothing) — wasted-but-safe, like finished
    lanes inside a fused burst.

    Transfer discipline (both directions measured material on
    high-latency links — ~12 ms/burst for nine small uploads vs one):
    the int32 inputs arrive as ONE packed array,
    ``packed_i32 = [window | block_tables | wlen, seq_lens, budget,
    gate_open, top_k]`` (columns ``[:W]``, ``[W:W+table_w]``, then five
    per-lane scalars), plus one f32 ``fparams = (temperature, top_p)``.
    Returns ``(packed [rounds, b, spec_k+4] int32, k_pages, v_pages)``
    where ``packed[..., :k+1]`` are the emitted tokens and
    ``packed[..., k+1:k+4]`` are (emit_len, prop_len, accepted) — ONE
    array so the burst costs a single blocking device→host fetch.
    """
    W = packed_i32.shape[1] - table_w - 5
    window = packed_i32[:, :W]
    block_tables = packed_i32[:, W : W + table_w]
    wlen = packed_i32[:, W + table_w]
    seq_lens = packed_i32[:, W + table_w + 1]
    budgets = packed_i32[:, W + table_w + 2]
    gate_open = packed_i32[:, W + table_w + 3].astype(bool)
    top_k = packed_i32[:, W + table_w + 4]
    temperature = fparams[:, 0]
    top_p = fparams[:, 1]
    b = window.shape[0]
    n = ngram
    k = spec_k
    # Window-base offset: window[j] holds the token at global position
    # base + j. Both wlen and seq_lens advance by emit_len per round, so
    # base is constant across the scan.
    base = seq_lens - wlen  # [b]

    def round_body(carry, key):
        window, wlen, seq_lens, budget, k_pages, v_pages = carry
        active = seq_lens > 0

        # ---- propose (vectorized prompt lookup over the window) --------
        patt_idx = wlen[:, None] - n + jnp.arange(n)[None, :]  # [b, n]
        pattern = jnp.take_along_axis(
            window, jnp.clip(patt_idx, 0, W - 1), axis=1
        )  # [b, n]
        j = jnp.arange(W)[None, :]  # candidate match starts (window coords)
        m = jnp.ones((b, W), bool)
        for o in range(n):  # ngram is static and small
            wo = jnp.take_along_axis(window, jnp.clip(j + o, 0, W - 1), axis=1)
            m = m & (wo == pattern[:, o : o + 1]) & (j + o < W)
        # Host-parity validity: start <= len-n-1 (terminal occurrence
        # excluded) and start >= len-1-max_scan (in global coords).
        m = m & (j + n <= wlen[:, None] - 1)
        m = m & (j + base[:, None] >= seq_lens[:, None] - 1 - max_scan)
        latest = jnp.max(jnp.where(m, j, -1), axis=1)  # [b]
        has = latest >= 0
        avail = wlen - (latest + n)  # followers available (>= 1 when has)
        # Budget clamp mirrors the host: drafts past budget-1 can never be
        # emitted (the verify emits accepted+1).
        prop_len = jnp.where(
            has & gate_open & active,
            jnp.minimum(jnp.minimum(k, avail), jnp.maximum(budget - 1, 0)),
            0,
        ).astype(jnp.int32)
        didx = latest[:, None] + n + jnp.arange(k)[None, :]
        drafts = jnp.take_along_axis(
            window, jnp.clip(didx, 0, W - 1), axis=1
        )  # [b, k] (garbage beyond prop_len — masked below)

        # ---- build the verify chunk ------------------------------------
        last_tok = jnp.take_along_axis(
            window, jnp.clip(wlen - 1, 0, W - 1)[:, None], axis=1
        )[:, 0]
        chunk = jnp.concatenate(
            [last_tok[:, None], drafts,
             jnp.zeros((b, s_chunk - 1 - k), jnp.int32)],
            axis=1,
        )  # [b, s_chunk]
        n_chunk = 1 + prop_len
        jj = jnp.arange(s_chunk)[None, :]
        valid = (jj < n_chunk[:, None]) & active[:, None]
        start = jnp.maximum(seq_lens - 1, 0)
        positions = start[:, None] + jj  # [b, s_chunk]
        P = block_tables.shape[1]
        page_ids = jnp.take_along_axis(
            block_tables, jnp.clip(positions // page_size, 0, P - 1), axis=1
        )
        slot_ids = positions % page_size
        # Scales stay None: the engine rejects spec_decode + KV_QUANT_HBM.
        h, k_pages, v_pages, *_ = _prefill_body(
            params, cfg, chunk, positions, valid, k_pages, v_pages,
            page_ids, slot_ids, block_tables, start, mesh, attn_impl,
            interpret,
        )
        logits = _logits(params, cfg, h)  # [b, s_chunk, vocab] f32

        # ---- accept ----------------------------------------------------
        # logits[j] predict the token AFTER chunk[j]; the draft under test
        # there is chunk[j+1], so drafts shift left by one.
        drafts_shift = jnp.concatenate(
            [chunk[:, 1:], jnp.zeros((b, 1), jnp.int32)], axis=1
        )

        # ``spec_sample`` gates itself: an all-greedy burst skips the
        # filtered-distribution sorts entirely.
        accept, replacement, free = spec_sample(
            logits, drafts_shift, temperature, top_k, top_p, key
        )
        lead = jnp.cumprod(accept[:, :k].astype(jnp.int32), axis=1)  # [b, k]
        acc = jnp.sum(
            lead * (jnp.arange(k)[None, :] < prop_len[:, None]), axis=1
        ).astype(jnp.int32)  # leading accepts among the real drafts
        corrected = jnp.where(
            acc < prop_len,
            jnp.take_along_axis(replacement, acc[:, None], axis=1)[:, 0],
            jnp.take_along_axis(free, acc[:, None], axis=1)[:, 0],
        )
        kk = jnp.arange(k + 1)[None, :]
        drafts_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1
        )
        emit = jnp.where(
            kk < acc[:, None],
            drafts_pad,
            jnp.where(kk == acc[:, None], corrected[:, None], 0),
        )  # [b, k+1]
        emit_len = jnp.where(
            active & (budget > 0), jnp.minimum(acc + 1, budget), 0
        ).astype(jnp.int32)

        # ---- commit on device (window / lengths / budget) --------------
        rows = jnp.arange(b)[:, None]
        widx = jnp.clip(wlen[:, None] + kk, 0, W - 1)
        cur = jnp.take_along_axis(window, widx, axis=1)
        updates = jnp.where(kk < emit_len[:, None], emit, cur)
        window = window.at[rows, widx].set(updates)
        wlen = wlen + emit_len
        seq_lens = seq_lens + emit_len
        budget = budget - emit_len

        return (
            (window, wlen, seq_lens, budget, k_pages, v_pages),
            (emit, emit_len, prop_len, acc),
        )

    keys = jax.random.split(rng_key, num_rounds)
    (_, _, _, _, k_pages, v_pages), (emit, emit_len, prop_len, acc) = (
        jax.lax.scan(
            round_body,
            (window, wlen, seq_lens, budgets, k_pages, v_pages),
            keys,
        )
    )
    packed = jnp.concatenate(
        [emit, emit_len[..., None], prop_len[..., None], acc[..., None]],
        axis=-1,
    )
    return packed, k_pages, v_pages


def _denoise_body(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, B] int32 — each lane's block, masks included
    seq_lens: jnp.ndarray,  # [b] int32 — final context (a multiple of B)
    active: jnp.ndarray,  # [b] bool — False: a padded lane, writes nothing
    block_tables: jnp.ndarray,  # [b, pages] int32, covering the block too
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_size: int,
    mesh,
    attn_impl: str,
    interpret: bool,
    experts_touched: Optional[list] = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One forward of a block of ``B = cfg.block_length`` rows a lane at
    positions ``seq_lens .. seq_lens + B - 1`` against the paged context
    (``_prefill_body``, the forward ``spec_decode_steps``' verify makes):
    every row sees the whole context and the whole block. Returns (logits
    at every row [b, B, vocab] f32, k_pages, v_pages) with the block's keys
    and values written at its positions. Those of a block that still has
    masked rows lie beyond ``seq_lens`` in pages the sequence owns and are
    bookkeeping: nothing reads them, and the forward that finds no row
    masked writes the final ones over them."""
    b, width = tokens.shape
    positions = seq_lens[:, None] + jnp.arange(width)[None, :]
    valid = jnp.broadcast_to(active[:, None], (b, width))
    page_ids = jnp.take_along_axis(
        block_tables,
        jnp.clip(positions // page_size, 0, block_tables.shape[1] - 1),
        axis=1,
    )
    h, k_pages, v_pages, *_ = _prefill_body(
        params, cfg, tokens, positions, valid, k_pages, v_pages,
        page_ids, positions % page_size, block_tables, seq_lens, mesh,
        attn_impl, interpret, experts_touched=experts_touched,
    )
    return _logits(params, cfg, h), k_pages, v_pages


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "mesh", "attn_impl", "interpret"),
    donate_argnames=("k_pages", "v_pages"),
)
def denoise_step(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, block_length] int32
    seq_lens: jnp.ndarray,  # [b] int32 — final context length
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [b, pages] int32
    *,
    page_size: int,
    mesh=None,
    attn_impl: str = "xla",
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The single denoising / committing forward as a logits API (what
    ``decode_step`` is to ``decode_steps``): (logits [b, block_length,
    vocab], k_pages, v_pages). Confidence and transfer stay with the
    caller; ``denoise_steps`` is the served program over the same body."""
    return _denoise_body(
        params, cfg, tokens, seq_lens, jnp.ones(tokens.shape[:1], bool),
        block_tables, k_pages, v_pages, page_size, mesh, attn_impl,
        interpret,
    )


#: ``denoise_steps``' per-lane ``active`` word of a lane whose block is the
#: one the dispatch before left on the device (1: the block is the host's).
BLOCK_CARRIED = 2


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "page_size", "table_w", "mesh", "attn_impl", "interpret",
    ),
    donate_argnames=("k_pages", "v_pages"),
)
def denoise_steps(
    params: Params,
    cfg: LlamaConfig,
    packed_i32: jnp.ndarray,  # [b, 2 * B + table_w + 5] int32 — see below
    fparams: jnp.ndarray,  # [b, 3] f32 — (threshold, temperature, top_p)
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    rng_key: jax.Array,
    *,
    page_size: int,
    table_w: int,  # block-table width inside packed_i32
    mesh=None,
    attn_impl: str = "xla",
    interpret: bool = False,
    carried: Optional[jnp.ndarray] = None,  # [b, 2 * B + 1] int32 — see below
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One step of generation by diffusion over blocks for every lane: the
    served program (``B = cfg.block_length``).

    Per lane: one forward of its block against the paged context
    (``_denoise_body``), then, on the device, each row's candidate token
    and its probability under the row's own softmax (``block_candidates``)
    and the rows this step fixes (``block_transfer``: every masked row over
    the lane's threshold if they are at least the number the schedule owes,
    else that many of the most confident). A fixed row is never masked
    again. A lane whose block has no masked row fixes nothing: its forward
    is the COMMITTING one, whose keys and values are final; the host then
    opens the lane's next block.

    Everything a request can set is data, so one program serves every mix
    of steps, thresholds and sampling per (lanes, table width). Packed as
    ``spec_decode_steps`` packs: ``packed_i32 = [tokens (B) | masked (B) |
    block_tables | seq_len, step, denoising_steps, top_k, active]``, one
    f32 ``fparams``. Returns ``(packed [b, 2 * B + 1] int32, k_pages,
    v_pages)``: the block's tokens after the step, its rows still masked,
    and in the last column the distinct experts this forward's rows chose,
    summed over the layers (the same number in every lane; 0 where the FFN
    is not the routed one) — one array, one fetch.

    ``carried``: the ``packed`` the dispatch before this one returned, still
    on the device. A lane whose ``active`` word is ``BLOCK_CARRIED`` takes
    its block (tokens, rows masked) from there and not from ``packed_i32``,
    so the engine can enqueue this forward before it has fetched that one
    (``Engine._run_decode_block``); ``seq_len``, ``step`` and the table are
    the host's for every lane. None (the references' call): every lane's
    block is ``packed_i32``'s."""
    width = cfg.block_length
    tail = 2 * width + table_w
    tokens = packed_i32[:, :width]
    masked = packed_i32[:, width : 2 * width] != 0
    if carried is not None:
        from_carried = (packed_i32[:, tail + 4] == BLOCK_CARRIED)[:, None]
        tokens = jnp.where(from_carried, carried[:, :width], tokens)
        masked = jnp.where(
            from_carried, carried[:, width : 2 * width] != 0, masked
        )
    block_tables = packed_i32[:, 2 * width : tail]
    seq_lens = packed_i32[:, tail]
    step = packed_i32[:, tail + 1]
    steps = packed_i32[:, tail + 2]
    top_k = packed_i32[:, tail + 3]
    active = packed_i32[:, tail + 4] != 0
    threshold, temperature, top_p = fparams[:, 0], fparams[:, 1], fparams[:, 2]

    touched = []
    logits, k_pages, v_pages = _denoise_body(
        params, cfg, tokens, seq_lens, active, block_tables, k_pages,
        v_pages, page_size, mesh, attn_impl, interpret,
        experts_touched=touched,
    )
    candidate, prob = block_candidates(
        logits, temperature, top_k, top_p, rng_key
    )
    fix = block_transfer(prob, masked, step, steps, threshold)
    fix = fix & active[:, None]
    n_touched = sum(touched, jnp.zeros((), jnp.int32))
    if n_touched.ndim:  # a routed layer that counts its places too
        n_touched = n_touched[0]
    packed = jnp.concatenate(
        [
            jnp.where(fix, candidate, tokens),
            (masked & ~fix).astype(jnp.int32),
            jnp.broadcast_to(n_touched, (tokens.shape[0], 1)),
        ],
        axis=1,
    )
    return packed, k_pages, v_pages
