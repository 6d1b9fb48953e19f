"""HuggingFace → native parameter conversion for Llama-family checkpoints.

Maps a transformers Llama/Qwen2/Qwen3/Mixtral/DeepSeek-V3/LFM2-MoE/
LongCat-Flash state dict onto the pytree layout of ``models/llama.py``. torch ``Linear`` stores ``[out, in]`` and computes
``x @ W.T``; our params store ``[in, out]``, so every projection transposes.
The RoPE convention (half-split rotate) matches HF Llama, so no permutation
of head channels is needed.
"""

from __future__ import annotations

import re
from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from .llama import LlamaConfig, Params


def _to_np(t) -> np.ndarray:
    """torch tensor / array-like → numpy (host)."""
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, np.float32)


def load_hf_state_dict(
    state_dict: Mapping[str, Any], cfg: LlamaConfig
) -> Params:
    sd = state_dict
    if cfg.router_before_attention:
        return _load_smallthinker(sd, cfg)
    if cfg.n_kda_layers and not cfg.kv_lora_rank:
        return _load_solar_open2(sd, cfg)
    if cfg.sliding_window:
        return _load_afmoe(sd, cfg)
    if cfg.layer_types is not None:
        return _load_lfm2_moe(sd, cfg)
    if cfg.double_layer:
        return _load_longcat_flash(sd, cfg)

    def get(name: str) -> np.ndarray:
        return _to_np(sd[name])

    def linear(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name).T, cfg.dtype)  # [out,in] -> [in,out]

    def stacked(prefix: str, name: str) -> jnp.ndarray:
        return jnp.stack(
            [linear(f"{prefix}experts.{j}.{name}") for j in range(cfg.n_experts)]
        )

    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        layer = {
            "attn_norm": jnp.asarray(get(p + "input_layernorm.weight"), cfg.dtype),
            "wo": linear(p + "self_attn.o_proj.weight"),
            "mlp_norm": jnp.asarray(get(p + "post_attention_layernorm.weight"), cfg.dtype),
        }
        if cfg.kv_lora_rank and cfg.q_lora_rank:
            # DeepSeek-V3's low-rank query path: down, its norm, up
            layer["wq_a"] = linear(p + "self_attn.q_a_proj.weight")
            layer["q_a_norm"] = jnp.asarray(
                get(p + "self_attn.q_a_layernorm.weight"), cfg.dtype
            )
            layer["wq_b"] = linear(p + "self_attn.q_b_proj.weight")
        else:
            layer["wq"] = linear(p + "self_attn.q_proj.weight")
        if cfg.kv_lora_rank:
            # DeepSeek-V3's latent attention: the down-projection carries
            # the shared rope key (``_with_mqa``), its norm is the latent's
            # alone; ``q_proj`` and ``kv_b_proj`` are laid out a head as
            # ``[nope | rope]`` and ``[k_nope | v]``, as ``_mla_project``
            # and ``_mla_kvb`` split them.
            layer["wkv_a"] = linear(p + "self_attn.kv_a_proj_with_mqa.weight")
            layer["kv_norm"] = jnp.asarray(
                get(p + "self_attn.kv_a_layernorm.weight"), cfg.dtype
            )
            layer["wkv_b"] = linear(p + "self_attn.kv_b_proj.weight")
        else:
            layer["wk"] = linear(p + "self_attn.k_proj.weight")
            layer["wv"] = linear(p + "self_attn.v_proj.weight")
        if cfg.n_experts and i >= cfg.first_k_dense:
            # Expert weights stacked to [E, d, f] / [E, f, d] for the
            # masked-dense expert einsum. Three checkpoint namings:
            # - Mixtral: block_sparse_moe.gate + experts.j.{w1,w3,w2}
            # - Qwen3-MoE: mlp.gate + mlp.experts.j.{gate,up,down}_proj
            # - DeepSeek-V3: Qwen3-MoE's names, and beside them the router's
            #   correction bias (float32, never cast) and
            #   mlp.shared_experts.{gate,up,down}_proj
            if f"{p}block_sparse_moe.gate.weight" in sd:
                moe = p + "block_sparse_moe."
                names = ("w1.weight", "w3.weight", "w2.weight")
            else:
                moe = p + "mlp."
                names = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
            layer["router"] = linear(moe + "gate.weight")
            layer["w_gate"] = stacked(moe, names[0])
            layer["w_up"] = stacked(moe, names[1])
            layer["w_down"] = stacked(moe, names[2])
            if cfg.moe_scoring == "sigmoid":
                layer["router_bias"] = jnp.asarray(
                    get(moe + "gate.e_score_correction_bias"), jnp.float32
                )
            if cfg.n_shared_experts:
                for ours, theirs in zip(("gate", "up", "down"), names):
                    layer[f"ws_{ours}"] = linear(f"{moe}shared_experts.{theirs}")
        else:
            layer["w_gate"] = linear(p + "mlp.gate_proj.weight")
            layer["w_up"] = linear(p + "mlp.up_proj.weight")
            layer["w_down"] = linear(p + "mlp.down_proj.weight")
        if cfg.qkv_bias:
            layer["bq"] = jnp.asarray(get(p + "self_attn.q_proj.bias"), cfg.dtype)
            layer["bk"] = jnp.asarray(get(p + "self_attn.k_proj.bias"), cfg.dtype)
            layer["bv"] = jnp.asarray(get(p + "self_attn.v_proj.bias"), cfg.dtype)
        if cfg.qk_norm:
            layer["q_norm"] = jnp.asarray(get(p + "self_attn.q_norm.weight"), cfg.dtype)
            layer["k_norm"] = jnp.asarray(get(p + "self_attn.k_norm.weight"), cfg.dtype)
        layers.append(layer)

    params: Params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), cfg.dtype),
        "final_norm": jnp.asarray(get("model.norm.weight"), cfg.dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = linear("lm_head.weight")
    return params


def _load_longcat_flash(sd: Mapping[str, Any], cfg: LlamaConfig) -> Params:
    """``model_type: longcat_flash``: a published layer is two attentions,
    two dense FFNs and one routed FFN (``llama.init_params``' double layer:
    the first half at the top, ``moe``, ``second``). A process that holds a
    range of the experts (``cfg.expert_first`` / ``expert_count``) loads its
    own and the whole router. ASSUMED, with no network at hand to read the
    checkpoint's index: the names below are those of the family's published
    modelling code (``modeling_longcat_flash.py``: ``self_attn.{0,1}``,
    ``input_layernorm.{0,1}``, ``post_attention_layernorm.{0,1}``,
    ``mlps.{0,1}``, ``mlp.router.classifier`` with
    ``mlp.router.e_score_correction_bias``, ``mlp.experts.N``); a checkpoint
    that names them otherwise fails on the missing key, named. Every key of
    the state dict that is not mapped (the audio and vision towers, the codec
    decoder, a multi-token-prediction head, an expert this process does not
    hold excepted) is refused by name."""
    used = set()

    def get(name: str) -> np.ndarray:
        used.add(name)
        return _to_np(sd[name])

    def vector(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name), cfg.dtype)

    def linear(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name).T, cfg.dtype)  # [out,in] -> [in,out]

    ffn = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))

    def half(p: str, j: int) -> dict:
        a = f"{p}self_attn.{j}."
        part = {
            "attn_norm": vector(f"{p}input_layernorm.{j}.weight"),
            "wq_a": linear(a + "q_a_proj.weight"),
            "q_a_norm": vector(a + "q_a_layernorm.weight"),
            "wq_b": linear(a + "q_b_proj.weight"),
            "wkv_a": linear(a + "kv_a_proj_with_mqa.weight"),
            "kv_norm": vector(a + "kv_a_layernorm.weight"),
            "wkv_b": linear(a + "kv_b_proj.weight"),
            "wo": linear(a + "o_proj.weight"),
            "mlp_norm": vector(f"{p}post_attention_layernorm.{j}.weight"),
        }
        for ours, theirs in ffn:
            part[ours] = linear(f"{p}mlps.{j}.{theirs}.weight")
        return part

    held = range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        moe = {
            "router": linear(p + "mlp.router.classifier.weight"),
            "router_bias": jnp.asarray(
                get(p + "mlp.router.e_score_correction_bias"), jnp.float32
            ),
        }
        for ours, theirs in ffn:
            moe[ours] = jnp.stack([
                linear(f"{p}mlp.experts.{e}.{theirs}.weight") for e in held
            ])
        layers.append({**half(p, 0), "moe": moe, "second": half(p, 1)})
    params = {
        "embed": vector("model.embed_tokens.weight"),
        "final_norm": vector("model.norm.weight"),
        "layers": layers,
        "lm_head": linear("lm_head.weight"),
    }
    # an expert of a layer that is run, held by another process
    elsewhere = re.compile(r"model\.layers\.(\d+)\.mlp\.experts\.(\d+)\.")

    def held_elsewhere(key: str) -> bool:
        m = elsewhere.match(key)
        return bool(m) and int(m[1]) < cfg.n_layers and (
            int(m[2]) < cfg.n_experts and int(m[2]) not in held
        )

    unmapped = sorted(
        k for k in sd if k not in used and not held_elsewhere(k)
    )
    if unmapped:
        raise NotImplementedError(
            f"{len(unmapped)} keys of the checkpoint are not part of the "
            f"language model that is run (towers, decoders and further "
            f"heads are outside): {unmapped[:4]}"
        )
    return params


def _longcat_flash_config(hf_config, rope_scaling) -> LlamaConfig:
    """``model_type: longcat_flash``, from the keys its published
    ``config.json`` has (``num_layers``, ``ffn_hidden_size``,
    ``expert_ffn_hidden_size``, ``moe_topk``, ``zero_expert_num``...). What
    the program does not run is refused here by name."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    if has("zero_expert_num", 0) and has("zero_expert_type", "identity") != "identity":
        raise NotImplementedError(
            f"zero_expert_type={has('zero_expert_type')!r}: only identity "
            "experts are supported"
        )
    if has("attention_method", "MLA") != "MLA":
        raise NotImplementedError(
            f"attention_method={has('attention_method')!r} is not supported "
            "yet (MLA)"
        )
    if has("attention_bias", False) or has("router_bias", False):
        raise NotImplementedError(
            "attention_bias / router_bias (biased projections) are not "
            "supported yet"
        )
    if rope_scaling is not None:
        raise NotImplementedError("rope_scaling with latent attention")
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.ffn_hidden_size,
        n_layers=hf_config.num_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_attention_heads,
        head_dim=hf_config.qk_rope_head_dim,
        rope_theta=float(has("rope_theta", 10_000_000.0)),
        rms_norm_eps=has("rms_norm_eps", 1e-5),
        n_experts=hf_config.n_routed_experts,
        n_experts_per_tok=hf_config.moe_topk,
        moe_intermediate_size=hf_config.expert_ffn_hidden_size,
        norm_topk_prob=bool(has("norm_topk_prob", False)),
        kv_lora_rank=hf_config.kv_lora_rank,
        q_lora_rank=hf_config.q_lora_rank,
        mla_scale_q_lora=bool(has("mla_scale_q_lora", False)),
        mla_scale_kv_lora=bool(has("mla_scale_kv_lora", False)),
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        rope_interleave=bool(has("rope_interleave", True)),
        routed_scaling_factor=float(has("routed_scaling_factor", 1.0)),
        moe_router_bias=True,
        n_zero_experts=has("zero_expert_num", 0) or 0,
        double_layer=True,
    )


def _load_lfm2_moe(sd: Mapping[str, Any], cfg: LlamaConfig) -> Params:
    """``model_type: lfm2_moe`` (LFM2-8B-A1B). The checkpoint's names are
    written here AS THE AUTHOR REMEMBERS the published modelling code
    (``modeling_lfm2_moe.py``), with no network at hand to read it again:
    ``operator_norm`` / ``ffn_norm`` a layer; ``conv.in_proj`` (``[B | C |
    x]``, ``[3d, d]``), ``conv.conv`` (a depthwise ``Conv1d`` weight ``[d,
    1, K]``, cross-correlated, so tap ``K - 1`` weighs the newest token:
    ``conv_w`` is its transpose) and ``conv.out_proj``, or ``self_attn.{q,
    k, v, out}_proj`` with ``q_layernorm`` / ``k_layernorm``;
    ``feed_forward.w1 / w3 / w2`` (gate, up, down) in the leading dense
    layers, else ``feed_forward.gate`` (the router), ``feed_forward.
    expert_bias`` and ``feed_forward.experts.N.w1 / w3 / w2``;
    ``embedding_norm`` after the last layer; the head is the embedding. A
    checkpoint that names them otherwise fails on the missing key, named."""
    def get(name: str) -> np.ndarray:
        return _to_np(sd[name])

    def vector(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name), cfg.dtype)

    def linear(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name).T, cfg.dtype)  # [out,in] -> [in,out]

    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        layer = {
            "attn_norm": vector(p + "operator_norm.weight"),
            "mlp_norm": vector(p + "ffn_norm.weight"),
        }
        if cfg.layer_kind(i) == "conv":
            layer["conv_in"] = linear(p + "conv.in_proj.weight")
            # [d, 1, K] -> [K, d]
            layer["conv_w"] = jnp.asarray(
                get(p + "conv.conv.weight")[:, 0, :].T, cfg.dtype
            )
            layer["conv_out"] = linear(p + "conv.out_proj.weight")
        else:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "out_proj")):
                layer[ours] = linear(f"{p}self_attn.{theirs}.weight")
            layer["q_norm"] = vector(p + "self_attn.q_layernorm.weight")
            layer["k_norm"] = vector(p + "self_attn.k_layernorm.weight")
        ff = p + "feed_forward."
        names = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
        if i >= cfg.first_k_dense:
            layer["router"] = linear(ff + "gate.weight")
            layer["router_bias"] = jnp.asarray(
                get(ff + "expert_bias"), jnp.float32
            )
            for ours, theirs in names:
                layer[ours] = jnp.stack([
                    linear(f"{ff}experts.{j}.{theirs}.weight")
                    for j in range(cfg.n_experts)
                ])
        else:
            for ours, theirs in names:
                layer[ours] = linear(f"{ff}{theirs}.weight")
        layers.append(layer)
    return {
        "embed": vector("model.embed_tokens.weight"),
        "final_norm": vector("model.embedding_norm.weight"),
        "layers": layers,
    }


def _load_afmoe(sd: Mapping[str, Any], cfg: LlamaConfig) -> Params:
    """``model_type: afmoe`` (Trinity). The checkpoint's names are written
    here AS THE AUTHOR REMEMBERS the published modelling code
    (``modeling_afmoe.py``), with no network at hand to read it again: a
    layer's four norms ``input_layernorm`` (before the attention),
    ``post_attention_layernorm`` (on its output, before the residual add),
    ``pre_mlp_layernorm`` and ``post_mlp_layernorm``; ``self_attn.{q, k, v,
    o}_proj``, ``self_attn.gate_proj`` (the output gate) and ``self_attn.
    q_norm`` / ``k_norm``; ``mlp.{gate, up, down}_proj`` in the leading dense
    layers, else ``mlp.router.gate`` (the router), ``mlp.expert_bias``,
    ``mlp.experts.N.{gate, up, down}_proj`` and ``mlp.shared_experts.{gate,
    up, down}_proj``; ``model.norm`` and an untied ``lm_head``. A checkpoint
    that names them otherwise fails on the missing key, named. A held range
    of the experts (``expert_first`` / ``expert_count``) reads those experts
    alone; a layer whose kind is sliding gets the leaf ``window``."""
    def get(name: str) -> np.ndarray:
        return _to_np(sd[name])

    def vector(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name), cfg.dtype)

    def linear(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name).T, cfg.dtype)  # [out,in] -> [in,out]

    ffn = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        layer = {
            "attn_norm": vector(p + "input_layernorm.weight"),
            "attn_post_norm": vector(p + "post_attention_layernorm.weight"),
            "mlp_norm": vector(p + "pre_mlp_layernorm.weight"),
            "mlp_post_norm": vector(p + "post_mlp_layernorm.weight"),
            "q_norm": vector(p + "self_attn.q_norm.weight"),
            "k_norm": vector(p + "self_attn.k_norm.weight"),
        }
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj"),
                             ("wg", "gate_proj")):
            layer[ours] = linear(f"{p}self_attn.{theirs}.weight")
        if cfg.layer_kind(i) == "sliding":
            layer["window"] = jnp.asarray(cfg.sliding_window, jnp.int32)
        if i >= cfg.first_k_dense:
            layer["router"] = linear(p + "mlp.router.gate.weight")
            layer["router_bias"] = jnp.asarray(
                get(p + "mlp.expert_bias"), jnp.float32
            )
            held = range(cfg.expert_first, cfg.expert_first + cfg.experts_held)
            for ours, theirs in ffn:
                layer["w_" + ours] = jnp.stack([
                    linear(f"{p}mlp.experts.{j}.{theirs}.weight") for j in held
                ])
                layer["ws_" + ours] = linear(
                    f"{p}mlp.shared_experts.{theirs}.weight"
                )
        else:
            for ours, theirs in ffn:
                layer["w_" + ours] = linear(f"{p}mlp.{theirs}.weight")
        layers.append(layer)
    return {
        "embed": vector("model.embed_tokens.weight"),
        "final_norm": vector("model.norm.weight"),
        "lm_head": linear("lm_head.weight"),
        "layers": layers,
    }


def _load_smallthinker(sd: Mapping[str, Any], cfg: LlamaConfig) -> Params:
    """``model_type: smallthinker``. The checkpoint's names are written here
    AS THE AUTHOR REMEMBERS the published modelling code
    (``modeling_smallthinker.py``), with no network at hand to read it again:
    a layer's two norms ``input_layernorm`` and ``post_attention_layernorm``;
    ``self_attn.{q, k, v, o}_proj``; ``block_sparse_moe.primary_router`` (the
    router, which reads the layer's input) and ``block_sparse_moe.experts.N.
    {gate, up, down}``; ``model.norm`` and an untied ``lm_head``. A checkpoint
    that names them otherwise fails on the missing key, named. A layer whose
    kind is sliding gets the leaf ``window``, every layer ``preroute``."""
    def get(name: str) -> np.ndarray:
        return _to_np(sd[name])

    def vector(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name), cfg.dtype)

    def linear(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name).T, cfg.dtype)  # [out,in] -> [in,out]

    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        layer = {
            "attn_norm": vector(p + "input_layernorm.weight"),
            "mlp_norm": vector(p + "post_attention_layernorm.weight"),
            "router": linear(p + "block_sparse_moe.primary_router.weight"),
            "preroute": jnp.asarray(1, jnp.int32),
        }
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            layer[ours] = linear(f"{p}self_attn.{theirs}.weight")
        if cfg.layer_kind(i) == "sliding":
            layer["window"] = jnp.asarray(cfg.sliding_window, jnp.int32)
        for name in ("gate", "up", "down"):
            layer["w_" + name] = jnp.stack([
                linear(f"{p}block_sparse_moe.experts.{j}.{name}.weight")
                for j in range(cfg.n_experts)
            ])
        layers.append(layer)
    params = {
        "embed": vector("model.embed_tokens.weight"),
        "final_norm": vector("model.norm.weight"),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = linear("lm_head.weight")
    return params


def _load_solar_open2(sd: Mapping[str, Any], cfg: LlamaConfig) -> Params:
    """``model_type: solar_open2``. The checkpoint's names are written here
    AS THE AUTHOR REMEMBERS Kimi Linear's published modelling code, whose
    linear layer this family runs, with no network at hand to read either
    again: a layer's two norms ``input_layernorm`` and
    ``post_attention_layernorm``; a GQA layer's ``self_attn.{q, k, v, g,
    o}_proj`` (``g_proj`` the output gate); a linear layer's ``self_attn.{q,
    k, v}_proj`` and ``{q, k, v}_conv1d.weight [channels, 1, taps]`` (the
    program holds ``[q | k | v]`` as one product and one filter), ``f_a_proj``
    / ``f_b_proj`` and ``g_a_proj`` / ``g_b_proj`` (the low-rank pairs),
    ``b_proj``, ``A_log``, ``dt_bias``, ``o_norm``, ``o_proj``; ``mlp.gate``
    with ``e_score_correction_bias``, ``mlp.experts.N.{gate, up, down}_proj``
    and ``mlp.shared_experts``; ``model.norm`` and an untied ``lm_head``. A
    checkpoint that names them otherwise fails on the missing key, named."""
    def get(name: str) -> np.ndarray:
        return _to_np(sd[name])

    def vector(name: str, dtype=None) -> jnp.ndarray:
        return jnp.asarray(get(name), dtype or cfg.dtype)

    def linear(name: str) -> jnp.ndarray:
        return jnp.asarray(get(name).T, cfg.dtype)  # [out,in] -> [in,out]

    f32 = jnp.float32
    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        layer = {
            "attn_norm": vector(p + "input_layernorm.weight"),
            "mlp_norm": vector(p + "post_attention_layernorm.weight"),
            "wo": linear(a + "o_proj.weight"),
        }
        if cfg.layer_kind(i) == "linear":
            layer.update(
                kda_qkv=jnp.concatenate(
                    [linear(f"{a}{t}_proj.weight") for t in "qkv"], axis=1),
                # [channels, 1, taps] -> [taps, channels], oldest tap first
                kda_conv_w=jnp.concatenate([
                    jnp.asarray(get(f"{a}{t}_conv1d.weight")[:, 0, :].T, cfg.dtype)
                    for t in "qkv"], axis=1),
                kda_wf_down=linear(a + "f_a_proj.weight"),
                kda_wf_up=linear(a + "f_b_proj.weight"),
                kda_dt_bias=vector(a + "dt_bias", f32),
                kda_A_log=vector(a + "A_log", f32).reshape(-1),
                kda_wb=linear(a + "b_proj.weight"),
                kda_wg_down=linear(a + "g_a_proj.weight"),
                kda_wg_up=linear(a + "g_b_proj.weight"),
                kda_o_norm=vector(a + "o_norm.weight"),
            )
        else:
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wg", "g_proj")):
                layer[ours] = linear(f"{a}{theirs}.weight")
        moe = p + "mlp."
        layer["router"] = linear(moe + "gate.weight")
        layer["router_bias"] = vector(moe + "gate.e_score_correction_bias", f32)
        first = cfg.expert_first
        for name in ("gate", "up", "down"):
            layer["w_" + name] = jnp.stack([
                linear(f"{moe}experts.{first + j}.{name}_proj.weight")
                for j in range(cfg.experts_held)
            ])
            layer["ws_" + name] = linear(f"{moe}shared_experts.{name}_proj.weight")
        layers.append(layer)
    return {
        "embed": vector("model.embed_tokens.weight"),
        "final_norm": vector("model.norm.weight"),
        "lm_head": linear("lm_head.weight"),
        "layers": layers,
    }


def _solar_open2_config(hf_config, rope_scaling) -> LlamaConfig:
    """``model_type: solar_open2``: ``gqa_layers`` says which layers are
    softmax GQA layers (every ``gqa_interval + 1``-th, the first of its
    period; the others are delta-rule linear attentions sized by the nested
    ``linear_attn_config``), ``use_rope`` false that nothing is rotated,
    ``use_gqa_gate`` that the GQA heads' output is gated; every layer routed
    over ``n_routed_experts`` sigmoid-scored experts beside shared ones. What
    the program does not run is refused here by name."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    n = hf_config.num_hidden_layers
    gqa = sorted(int(i) for i in has("gqa_layers") or ())
    period = int(has("gqa_interval", 3)) + 1
    if gqa != list(range(0, n, period)):
        raise NotImplementedError(
            f"gqa_layers {gqa} with gqa_interval {period - 1}: a GQA layer "
            f"first in every period of {period} layers is supported"
        )
    linear = dict(has("linear_attn_config") or {})
    heads, kv_heads = linear.get("num_heads"), linear.get("num_kv_heads")
    if heads != hf_config.num_attention_heads or kv_heads not in (None, heads):
        raise NotImplementedError(
            f"linear_attn_config num_heads={heads} / num_kv_heads={kv_heads}: "
            "the attention heads for q, k and v alike are supported"
        )
    if has("use_rope", True) or rope_scaling is not None:
        raise NotImplementedError(
            "use_rope=true / rope_scaling with solar_open2 is not supported "
            "yet (no layer takes a position)"
        )
    if has("kda_use_full_proj", False) or not has("kda_allow_neg_eigval", True):
        raise NotImplementedError(
            "kda_use_full_proj=true / kda_allow_neg_eigval=false is not "
            "supported yet (low-rank gate projections, beta in (0, 2))"
        )
    if not has("use_gqa_gate", True) or has("first_k_dense_replace", 0):
        raise NotImplementedError(
            "use_gqa_gate=false / first_k_dense_replace > 0 is not supported yet"
        )
    if has("scoring_func", "sigmoid") != "sigmoid" or has("n_group", 1) != 1:
        raise NotImplementedError(
            f"scoring_func={has('scoring_func')!r} / n_group={has('n_group')}: "
            "sigmoid scores in one group are supported"
        )
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        n_layers=n,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=has("head_dim"),
        rope_theta=float(has("rope_theta", 10_000.0)),
        rms_norm_eps=has("rms_norm_eps", 1e-5),
        tie_word_embeddings=bool(has("tie_word_embeddings", False)),
        n_experts=hf_config.n_routed_experts,
        n_experts_per_tok=hf_config.num_experts_per_tok,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        norm_topk_prob=bool(has("norm_topk_prob", True)),
        n_shared_experts=has("n_shared_experts", 0) or 0,
        moe_scoring="sigmoid",
        routed_scaling_factor=float(has("routed_scaling_factor", 1.0)),
        layer_types=tuple(
            "full_attention" if i % period == 0 else "linear_attention"
            for i in range(n)
        ),
        kda_head_dim=linear["head_dim"],
        kda_conv_kernel=linear["short_conv_kernel_size"],
        kda_lora=True,
        kda_channel_gate=True,
        kda_neg_eigval=True,
        attn_output_gate=True,
        no_rope=True,
    )


def _smallthinker_config(hf_config, rope_scaling) -> LlamaConfig:
    """``model_type: smallthinker``: ``sliding_window_layout`` says which
    layers see a window of ``sliding_window_size`` positions, ``rope_layout``
    which rotate; every layer's FFN is ``moe_num_primary_experts`` ReGLU
    experts, ``moe_num_active_primary_experts`` a token, chosen by a router
    that reads the layer's input before the attention. What the program does
    not run is refused here by name: the program rotates a layer exactly
    where it slides (``llama._rotates``), so a file whose two layouts differ
    is not this program's model."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    n = hf_config.num_hidden_layers
    slides = [int(x) for x in has("sliding_window_layout") or ()]
    rotates = [int(x) for x in has("rope_layout") or ()]
    if len(slides) != n or set(slides) - {0, 1}:
        raise NotImplementedError(
            f"sliding_window_layout {slides}: a 0 or a 1 a layer, "
            f"{n} of them, is supported"
        )
    if rotates != slides:
        raise NotImplementedError(
            f"rope_layout {rotates} is not sliding_window_layout {slides}: "
            "a layer that rotates is one that slides, and no other, here"
        )
    if not any(slides):
        raise NotImplementedError(
            "sliding_window_layout without a sliding layer: with rope_layout "
            "equal to it no layer takes a position, which is not supported"
        )
    if not has("moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "moe_primary_router_apply_softmax=false is not supported yet "
            "(a softmax over the chosen logits)"
        )
    if not has("norm_topk_prob", True):
        raise NotImplementedError("norm_topk_prob=false is not supported yet")
    for key in ("moe_enable_secondary_experts", "moe_num_secondary_experts",
                "moe_num_active_secondary_experts"):
        if has(key):
            raise NotImplementedError(
                f"{key}={has(key)!r}: a secondary level of experts is not "
                "supported yet"
            )
    layout = has("moe_layer_layout")
    if layout is not None and not all(layout):
        raise NotImplementedError(
            "moe_layer_layout with a dense layer is not supported yet"
        )
    if rope_scaling is not None or getattr(hf_config, "rope_scaling", None):
        raise NotImplementedError("rope_scaling with sliding layers")
    width = hf_config.moe_ffn_hidden_size
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=width,  # the file has no dense FFN
        n_layers=n,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=has("head_dim"),
        rope_theta=float(has("rope_theta", 10_000.0)),
        rms_norm_eps=has("rms_norm_eps", 1e-6),
        tie_word_embeddings=bool(has("tie_word_embeddings", False)),
        n_experts=hf_config.moe_num_primary_experts,
        n_experts_per_tok=hf_config.moe_num_active_primary_experts,
        moe_intermediate_size=width,
        norm_topk_prob=True,
        hidden_act="relu",
        layer_types=tuple(
            "sliding_attention" if x else "full_attention" for x in slides
        ),
        sliding_window=hf_config.sliding_window_size,
        router_before_attention=True,
    )


def _afmoe_config(hf_config, rope_scaling) -> LlamaConfig:
    """``model_type: afmoe``: window and full attention layers in one model
    (``layer_types``), a gate on the attention's output, four norms a layer,
    the embedding times sqrt(hidden), leading dense layers, sigmoid-routed
    experts with a bias that chooses beside shared experts. What the
    program does not run is refused here by name."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    kinds = tuple(has("layer_types") or ())
    unknown = sorted(set(kinds) - {"sliding_attention", "full_attention"})
    if unknown or len(kinds) != hf_config.num_hidden_layers:
        raise NotImplementedError(
            f"layer_types {unknown or len(kinds)}: one of 'sliding_attention' "
            "/ 'full_attention' a layer is supported"
        )
    if has("score_func", "sigmoid") != "sigmoid":
        raise NotImplementedError(
            f"score_func={has('score_func')!r} is not supported yet (sigmoid)"
        )
    if not has("mup_enabled", False):
        raise NotImplementedError(
            "mup_enabled=false is not supported yet (the embedding is scaled "
            "by sqrt(hidden))"
        )
    if rope_scaling is not None:
        raise NotImplementedError("rope_scaling with sliding layers")
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=has("head_dim"),
        rope_theta=float(has("rope_theta", 10_000.0)),
        rms_norm_eps=has("rms_norm_eps", 1e-5),
        qk_norm=True,
        scale_embeddings=True,
        tie_word_embeddings=bool(has("tie_word_embeddings", False)),
        n_experts=hf_config.num_experts,
        n_experts_per_tok=hf_config.num_experts_per_tok,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        norm_topk_prob=bool(has("route_norm", True)),
        n_shared_experts=has("num_shared_experts", 0),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(has("route_scale", 1.0)),
        first_k_dense=has("num_dense_layers", 0),
        n_group=has("n_group", 1) or 1,
        topk_group=has("topk_group", 1) or 1,
        layer_types=kinds,
        sliding_window=hf_config.sliding_window,
        attn_output_gate=True,
        sandwich_norm=True,
    )


def _lfm2_moe_config(hf_config, rope_scaling) -> LlamaConfig:
    """``model_type: lfm2_moe``: gated short convolutions with some layers
    of GQA (``layer_types``), leading dense layers, sigmoid-routed experts
    with a bias that chooses. What the program does not run is refused here
    by name."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    if has("conv_bias", False):
        raise NotImplementedError(
            "conv_bias=true: a biased convolution is not supported yet"
        )
    if has("conv_L_cache", 3) < 2:
        raise NotImplementedError(
            f"conv_L_cache={has('conv_L_cache')}: a convolution needs at "
            "least two taps to keep state"
        )
    if not has("use_expert_bias", True):
        raise NotImplementedError(
            "use_expert_bias=false is not supported yet (the sigmoid router "
            "chooses by score + bias)"
        )
    kinds = tuple(has("layer_types"))
    unknown = sorted(set(kinds) - {"conv", "full_attention"})
    if unknown or len(kinds) != hf_config.num_hidden_layers:
        raise NotImplementedError(
            f"layer_types {unknown or len(kinds)}: one of 'conv' / "
            "'full_attention' a layer is supported"
        )
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.hidden_size // hf_config.num_attention_heads,
        rope_theta=float(has("rope_theta", 1_000_000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=has("norm_eps", 1e-5),
        qk_norm=True,
        tie_word_embeddings=bool(has("tie_word_embeddings", True)),
        n_experts=hf_config.num_experts,
        n_experts_per_tok=hf_config.num_experts_per_tok,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        norm_topk_prob=bool(has("norm_topk_prob", True)),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(has("routed_scaling_factor", 1.0)),
        router_norm_eps=1e-6,
        first_k_dense=has("num_dense_layers", 0),
        layer_types=kinds,
        conv_L_cache=has("conv_L_cache", 3),
    )


def config_from_hf(hf_config) -> LlamaConfig:
    """transformers LlamaConfig/Qwen2Config → native config."""
    rope_scaling = None
    rs = getattr(hf_config, "rope_scaling", None)
    if rs:
        rope_type = rs.get("rope_type", rs.get("type"))
        if rope_type == "llama3":
            from ..ops.rope import RopeScalingConfig

            rope_scaling = RopeScalingConfig(
                factor=rs["factor"],
                low_freq_factor=rs["low_freq_factor"],
                high_freq_factor=rs["high_freq_factor"],
                original_max_position=rs["original_max_position_embeddings"],
            )
        elif rope_type in (None, "default"):
            pass
        else:
            # Silently loading e.g. linear/dynamic/yarn scaling with base
            # frequencies would degrade long-context generation undetectably.
            raise NotImplementedError(
                f"rope_scaling type {rope_type!r} is not supported yet"
            )
    if getattr(hf_config, "model_type", "") == "lfm2_moe":
        return _lfm2_moe_config(hf_config, rope_scaling)
    if getattr(hf_config, "model_type", "") == "afmoe":
        return _afmoe_config(hf_config, rope_scaling)
    if getattr(hf_config, "model_type", "") == "smallthinker":
        return _smallthinker_config(hf_config, rope_scaling)
    if getattr(hf_config, "model_type", "") == "longcat_flash":
        return _longcat_flash_config(hf_config, rope_scaling)
    if getattr(hf_config, "model_type", "") == "solar_open2":
        return _solar_open2_config(hf_config, rope_scaling)
    if getattr(hf_config, "model_type", "") == "bailing_hybrid":
        return _bailing_hybrid_config(hf_config, rope_scaling)
    cls_name = hf_config.__class__.__name__
    is_gemma = cls_name == "GemmaConfig"
    if cls_name.startswith("Gemma") and not is_gemma:
        # Gemma2/3 change the layer schema (sandwich norms, softcapping,
        # sliding windows) — loading them as Gemma-1 would silently produce
        # wrong logits, same policy as the rope_scaling check above.
        raise NotImplementedError(
            f"{cls_name} is not supported yet (Gemma-1 only)"
        )
    # SDAR (``model_type: sdar_moe``): Qwen3-MoE's decoder, its weights
    # named as ``qwen3_moe``'s, generating by diffusion over blocks. The
    # published config gives neither block length nor mask id: the
    # family's released generation script's (4, 151669) unless it does.
    is_sdar = getattr(hf_config, "model_type", "") == "sdar_moe"
    latent = {}
    if getattr(hf_config, "model_type", "") == "deepseek_v3":
        latent = _deepseek_v3_fields(hf_config)
    hidden_act = getattr(hf_config, "hidden_activation", None) or getattr(
        hf_config, "hidden_act", "silu"
    )
    if hidden_act == "gelu_pytorch_tanh":
        hidden_act = "gelu_tanh"
    cfg = LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        head_dim=getattr(hf_config, "head_dim", None),
        rope_theta=getattr(hf_config, "rope_theta", 10_000.0),
        rope_scaling=rope_scaling,
        rms_norm_eps=hf_config.rms_norm_eps,
        qkv_bias=getattr(hf_config, "attention_bias", False)
        or hf_config.__class__.__name__.startswith("Qwen2"),
        qk_norm=hf_config.__class__.__name__.startswith("Qwen3") or is_sdar,
        tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        n_experts=getattr(hf_config, "num_local_experts", 0)
        or getattr(hf_config, "num_experts", 0)
        or getattr(hf_config, "n_routed_experts", 0),
        n_experts_per_tok=getattr(hf_config, "num_experts_per_tok", 2),
        moe_intermediate_size=getattr(hf_config, "moe_intermediate_size", None),
        norm_topk_prob=getattr(hf_config, "norm_topk_prob", True),
        # Passed through for every family; validated below so an unsupported
        # activation fails at load time, not on the first request.
        hidden_act=hidden_act,
        norm_offset=1.0 if is_gemma else 0.0,
        scale_embeddings=is_gemma,
        block_length=getattr(hf_config, "block_length", 4) if is_sdar else 0,
        mask_token_id=(
            getattr(hf_config, "mask_token_id", 151_669) if is_sdar else 0
        ),
        **latent,
    )
    cfg.act_fn  # raises ValueError for unsupported activations
    # Qwen3-MoE variants with partially-dense layers change the layer
    # schema; loading them as uniform-MoE would silently produce wrong
    # logits (same policy as the Gemma2/rope guards above).
    if cfg.n_experts:
        sparse_step = getattr(hf_config, "decoder_sparse_step", 1)
        dense_layers = getattr(hf_config, "mlp_only_layers", None) or []
        if sparse_step != 1 or dense_layers:
            raise NotImplementedError(
                "mixed dense/sparse decoder layers are not supported "
                f"(decoder_sparse_step={sparse_step}, "
                f"mlp_only_layers={list(dense_layers)})"
            )
        if getattr(hf_config, "shared_expert_intermediate_size", 0):
            # Qwen2-MoE adds an always-on shared expert; loading it as
            # routed-only would silently drop those weights.
            raise NotImplementedError(
                "shared-expert MoE (Qwen2-MoE style) is not supported"
            )
    return cfg


def _deepseek_v3_fields(hf_config) -> dict:
    """The fields of a ``deepseek_v3`` config that ``LlamaConfig`` carries
    beyond the common ones: latent attention, sigmoid routing with a
    correction bias, shared experts, leading dense layers. What the program
    does not run is refused here by name, not loaded as something else."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    if has("scoring_func", "sigmoid") != "sigmoid" or has(
        "topk_method", "noaux_tc"
    ) != "noaux_tc":
        raise NotImplementedError(
            f"scoring_func={has('scoring_func')!r} with topk_method="
            f"{has('topk_method')!r} is not supported yet (sigmoid, noaux_tc)"
        )
    if has("moe_layer_freq", 1) != 1:
        raise NotImplementedError(
            f"moe_layer_freq={has('moe_layer_freq')} is not supported yet"
        )
    return dict(
        kv_lora_rank=hf_config.kv_lora_rank,
        q_lora_rank=has("q_lora_rank"),
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        rope_interleave=bool(has("rope_interleave", True)),
        n_shared_experts=has("n_shared_experts", 0) or 0,
        moe_scoring="sigmoid",
        routed_scaling_factor=float(has("routed_scaling_factor", 1.0)),
        first_k_dense=has("first_k_dense_replace", 0),
        n_group=has("n_group", 1) or 1,
        topk_group=has("topk_group", 1) or 1,
    )


def _bailing_hybrid_config(hf_config, rope_scaling) -> LlamaConfig:
    """``model_type: bailing_hybrid`` (Ling-3.0): groups of
    ``layer_group_size`` layers, each a run of delta-rule linear attentions
    closed by one latent attention (layer ``i`` is latent where ``(i + 1) %
    layer_group_size == 0``), leading dense layers, then sigmoid-routed
    experts chosen within the best groups beside shared experts. What the
    program does not run is refused here by name; the multi-token-prediction
    module (``num_nextn_predict_layers``) is not part of the decoder and is
    not loaded."""
    def has(key, default=None):
        return getattr(hf_config, key, default)

    if has("score_function", "sigmoid") != "sigmoid" or not has(
        "moe_router_enable_expert_bias", True
    ):
        raise NotImplementedError(
            f"score_function={has('score_function')!r} / "
            "moe_router_enable_expert_bias=false is not supported yet "
            "(sigmoid scores with a bias that chooses)"
        )
    if has("num_kv_heads_for_linear_attn", 0) not in (
        0, hf_config.num_attention_heads
    ):
        raise NotImplementedError(
            "num_kv_heads_for_linear_attn other than the attention heads is "
            "not supported yet"
        )
    if has("group_norm_size", 1) != 1 or has(
        "gated_attention_proj_granularity_type", "head_wise"
    ) != "head_wise":
        raise NotImplementedError(
            "group_norm_size / gated_attention_proj_granularity_type other "
            "than 1 / head_wise is not supported yet"
        )
    if has("q_lora_rank") or rope_scaling is not None:
        raise NotImplementedError(
            "q_lora_rank / rope_scaling with linear layers is not supported yet"
        )
    for key in ("use_nGPT", "use_mla_nope", "value_norm", "up_proj_norm",
                "scale_router_input", "use_bias", "use_qkv_bias"):
        if has(key, False):
            raise NotImplementedError(f"{key}=true is not supported yet")
    if not (has("use_qk_norm", True) and has("linear_silu", True)):
        raise NotImplementedError(
            "use_qk_norm=false / linear_silu=false is not supported yet"
        )
    n, group = hf_config.num_hidden_layers, hf_config.layer_group_size
    limits = {
        name: tuple(has(key) or ()) or None
        for name, key in (
            ("expert_swiglu_limits", "expert_swiglu_limit_list"),
            ("shared_swiglu_limits", "share_expert_swiglu_limit_list"),
        )
    }
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        n_layers=n,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=has("head_dim"),
        rope_theta=float(has("rope_theta", 10_000.0)),
        rms_norm_eps=has("rms_norm_eps", 1e-6),
        tie_word_embeddings=bool(has("tie_word_embeddings", False)),
        n_experts=hf_config.num_experts,
        n_experts_per_tok=hf_config.num_experts_per_tok,
        moe_intermediate_size=hf_config.moe_intermediate_size,
        norm_topk_prob=bool(has("norm_topk_prob", True)),
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        rope_interleave=bool(has("rope_interleave", True)),
        n_shared_experts=has("num_shared_experts", 0) or 0,
        moe_scoring="sigmoid",
        routed_scaling_factor=float(has("routed_scaling_factor", 1.0)),
        n_group=has("n_group", 1) or 1,
        topk_group=has("topk_group", 1) or 1,
        first_k_dense=has("first_k_dense_replace", 0),
        layer_types=tuple(
            "full_attention" if (i + 1) % group == 0 else "linear_attention"
            for i in range(n)
        ),
        kda_head_dim=has("head_dim"),
        kda_conv_kernel=hf_config.short_conv_kernel_size,
        kda_safe_gate=bool(has("kda_safe_gate", False)),
        kda_lower_bound=float(has("kda_lower_bound", -5.0)),
        # the decay's projection as a low-rank pair of the head's size
        kda_lora=bool(has("use_kda_lora", False) or not has("no_kda_lora", True)),
        **limits,
    )
