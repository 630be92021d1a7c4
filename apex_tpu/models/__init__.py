"""Model zoo for the framework's recipes and benchmarks.

The reference has no model zoo of its own (it borrows torchvision resnets in
examples/imagenet/main_amp.py and BERT from NVIDIA DeepLearningExamples); a
standalone TPU framework must ship the models its recipes run, so they live
here.
"""

from .bert import (  # noqa: F401
    BertConfig, BertForPreTraining, BertModel, create_bert)
from .resnet import (  # noqa: F401
    BasicBlock, Bottleneck, ResNet, ResNet18, ResNet34, ResNet50, ResNet101,
    ResNet152, create_model)
from .transformer_lm import (  # noqa: F401
    TransformerBlock, TransformerLM, create_lm)
from .keye import KeyeLM  # noqa: F401
from .ling import LingLM  # noqa: F401
from .qwen3_next import Qwen3NextLM  # noqa: F401
from .zaya import ZayaLM  # noqa: F401


def build_lm(config: dict, **overrides):
    """The language model a published ``config.json`` describes, by its
    ``model_type``: ``gpt2`` -> :class:`TransformerLM` (``n_embd``,
    ``n_layer``, ``n_head``, ``n_positions``, ``vocab_size``), ``zaya`` ->
    :class:`ZayaLM` (``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``num_experts``, ``moe_intermediate_size``, ``router_hidden_size``,
    ``cca_time0/1``, ``rope_parameters``, ``partial_rotary_factor``,
    ``rms_norm_eps``, ``max_position_embeddings``, ``vocab_size``),
    ``qwen3_next`` -> :class:`Qwen3NextLM` (the full-attention keys as
    ``zaya``'s plus ``full_attention_interval``, ``linear_num_key_heads``,
    ``linear_num_value_heads``, ``linear_key_head_dim``,
    ``linear_value_head_dim``, ``linear_conv_kernel_dim``,
    ``num_experts_per_tok``, ``shared_expert_intermediate_size``,
    ``rope_theta``; ``num_experts`` is the count this chip HOLDS where the
    file states ``published.num_experts``, the router's width: the first
    ``num_experts`` ids are held unless ``experts_held`` is given),
    ``ling_v3`` -> :class:`LingLM` (Ling-3.0-flash's keys:
    ``layer_group_size``, ``first_k_dense_replace``, ``head_dim``,
    ``short_conv_kernel_size``, ``kda_lower_bound``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_theta``, ``intermediate_size``, ``moe_intermediate_size``,
    ``moe_shared_expert_intermediate_size``, ``num_experts_per_tok``,
    ``n_group``, ``topk_group``, ``routed_scaling_factor``; ``num_experts``
    and ``published.num_experts`` as for ``qwen3_next``; the published
    file names no ``model_type``, the name is this repo's), ``KeyeVL2``
    -> :class:`KeyeLM` (Keye-VL-2.0's language model: the full-attention
    keys as ``qwen3_next``'s, ``rope_theta``, ``num_experts_per_tok``,
    ``moe_intermediate_size`` and ``sa_config``'s ``indexer_num_heads``,
    ``indexer_head_dim`` and ``topk``; ``num_experts`` and
    ``published.num_experts`` as for ``qwen3_next``). A file may
    state ``published`` (the source's value of each key it cut) and
    ``changed`` (why), which only ``published.num_experts`` is read from.
    ``overrides`` are fields of the model class (``dtype``,
    ``experts_held``, ...). An unknown ``model_type`` raises."""
    kind = config.get("model_type")
    if kind == "gpt2":
        return TransformerLM(
            vocab_size=int(config["vocab_size"]),
            hidden=int(config["n_embd"]), num_layers=int(config["n_layer"]),
            num_heads=int(config["n_head"]),
            max_seq_len=int(config["n_positions"]), **overrides)
    if kind == "zaya":
        if int(config.get("num_experts_per_tok", 1)) != 1:
            raise NotImplementedError(
                "build_lm: the zaya expert layer routes top-1; got "
                f"num_experts_per_tok={config['num_experts_per_tok']}")
        rope = config["rope_parameters"]
        rope = rope.get("hybrid", rope)
        return ZayaLM(
            vocab_size=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]),
            num_layers=int(config["num_hidden_layers"]),
            num_heads=int(config["num_attention_heads"]),
            num_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            num_experts=int(config["num_experts"]),
            expert_width=int(config["moe_intermediate_size"]),
            router_width=int(config["router_hidden_size"]),
            cca_time0=int(config["cca_time0"]),
            cca_time1=int(config["cca_time1"]),
            rope_theta=float(rope["rope_theta"]),
            partial_rotary_factor=float(config["partial_rotary_factor"]),
            rms_eps=float(config["rms_norm_eps"]),
            max_seq_len=int(config["max_position_embeddings"]),
            **overrides)
    if kind == "qwen3_next":
        if not config.get("norm_topk_prob", True) \
                or config.get("mlp_only_layers") \
                or int(config.get("decoder_sparse_step", 1)) != 1 \
                or config.get("rope_scaling") \
                or config.get("tie_word_embeddings"):
            raise NotImplementedError(
                "build_lm: qwen3_next as published only - every layer "
                "sparse, renormalised top-k weights, default rotary "
                "scaling, an untied head")
        held = int(config["num_experts"])
        routed = int(config.get("published", {}).get("num_experts", held))
        if held != routed:
            overrides.setdefault("experts_held", tuple(range(held)))
        return Qwen3NextLM(
            vocab_size=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]),
            num_layers=int(config["num_hidden_layers"]),
            full_attention_interval=int(config["full_attention_interval"]),
            num_heads=int(config["num_attention_heads"]),
            num_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            partial_rotary_factor=float(config["partial_rotary_factor"]),
            rope_theta=float(config["rope_theta"]),
            lin_key_heads=int(config["linear_num_key_heads"]),
            lin_value_heads=int(config["linear_num_value_heads"]),
            lin_key_dim=int(config["linear_key_head_dim"]),
            lin_value_dim=int(config["linear_value_head_dim"]),
            conv_kernel=int(config["linear_conv_kernel_dim"]),
            num_experts=routed,
            experts_per_token=int(config["num_experts_per_tok"]),
            expert_width=int(config["moe_intermediate_size"]),
            shared_width=int(config["shared_expert_intermediate_size"]),
            rms_eps=float(config["rms_norm_eps"]),
            max_seq_len=int(config["max_position_embeddings"]),
            **overrides)
    if kind == "ling_v3":
        L = int(config["num_hidden_layers"])
        clamps = [v for key in ("expert_swiglu_limit_list",
                                "share_expert_swiglu_limit_list")
                  for v in list(config.get(key, ()))[:L]]
        if config.get("q_lora_rank") is not None \
                or config.get("score_function", "sigmoid") != "sigmoid" \
                or not config.get("norm_topk_prob", True) \
                or not config.get("moe_router_enable_expert_bias", True) \
                or config.get("gated_attention_proj_granularity_type",
                              "head_wise") != "head_wise" \
                or int(config.get("num_kv_heads_for_linear_attn", 0)) \
                or config.get("use_kda_lora") \
                or not config.get("kda_safe_gate", True) \
                or config.get("tie_word_embeddings") \
                or int(config.get("rotary_dim", config["qk_rope_head_dim"])
                       ) != int(config["qk_rope_head_dim"]) \
                or any(clamps):
            raise NotImplementedError(
                "build_lm: ling_v3 as published for the layers kept only - "
                "no query latent, a sigmoid router with a bias and "
                "renormalised weights, head-wise gates, as many key as "
                "value heads in the linear layers, a full decay matrix "
                "under the safe gate, rotary over the whole rotary key, an "
                "untied head, and no SwiGLU clamp on a kept layer")
        held = int(config["num_experts"])
        routed = int(config.get("published", {}).get("num_experts", held))
        if held != routed:
            overrides.setdefault("experts_held", tuple(range(held)))
        return LingLM(
            vocab_size=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]), num_layers=L,
            layer_group_size=int(config["layer_group_size"]),
            first_dense=int(config["first_k_dense_replace"]),
            num_heads=int(config["num_attention_heads"]),
            head_dim=int(config["head_dim"]),
            conv_kernel=int(config["short_conv_kernel_size"]),
            kda_lower_bound=float(config["kda_lower_bound"]),
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_dim=int(config["qk_nope_head_dim"]),
            qk_rope_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            rope_theta=float(config["rope_theta"]),
            dense_width=int(config["intermediate_size"]),
            num_experts=routed,
            experts_per_token=int(config["num_experts_per_tok"]),
            expert_width=int(config["moe_intermediate_size"]),
            shared_width=int(config["moe_shared_expert_intermediate_size"]),
            n_group=int(config["n_group"]),
            topk_group=int(config["topk_group"]),
            routed_scaling=float(config["routed_scaling_factor"]),
            rms_eps=float(config["rms_norm_eps"]),
            max_seq_len=int(config["max_position_embeddings"]),
            **overrides)
    if kind == "KeyeVL2":
        sa = config.get("sa_config") or {}
        rope = config.get("rope_scaling") or {}
        if int(config.get("decoder_sparse_step", 1)) != 1 \
                or config.get("mlp_only_layers") \
                or config.get("use_sliding_window") \
                or config.get("sliding_window") \
                or config.get("tie_word_embeddings") \
                or config.get("shared_expert_intermediate_size") \
                or not config.get("norm_topk_prob", True) \
                or config.get("attention_bias") \
                or rope.get("rope_type", "default") != "default" \
                or int(sa.get("indexer_num_kv_heads", 1)) != 1 \
                or not sa:
            raise NotImplementedError(
                "build_lm: KeyeVL2 as published only - every layer sparse "
                "attention (an indexer with one shared key) over experts, "
                "renormalised top-k weights and no shared expert, no "
                "sliding window, default rotary, no attention bias, an "
                "untied head")
        held = int(config["num_experts"])
        routed = int(config.get("published", {}).get("num_experts", held))
        if held != routed:
            overrides.setdefault("experts_held", tuple(range(held)))
        return KeyeLM(
            vocab_size=int(config["vocab_size"]),
            hidden=int(config["hidden_size"]),
            num_layers=int(config["num_hidden_layers"]),
            num_heads=int(config["num_attention_heads"]),
            num_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            rope_theta=float(config["rope_theta"]),
            index_heads=int(sa["indexer_num_heads"]),
            index_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]),
            num_experts=routed,
            experts_per_token=int(config["num_experts_per_tok"]),
            expert_width=int(config["moe_intermediate_size"]),
            rms_eps=float(config["rms_norm_eps"]),
            max_seq_len=int(config["max_position_embeddings"]),
            **overrides)
    raise ValueError(f"build_lm: no model for model_type {kind!r} "
                     "(have: 'gpt2', 'zaya', 'qwen3_next', 'ling_v3', "
                     "'KeyeVL2')")

__all__ = [
    "BasicBlock", "Bottleneck", "ResNet", "ResNet18", "ResNet34", "ResNet50",
    "ResNet101", "ResNet152", "create_model",
    "TransformerLM", "TransformerBlock", "create_lm", "ZayaLM",
    "Qwen3NextLM", "LingLM", "KeyeLM", "build_lm",
    "BertConfig", "BertModel", "BertForPreTraining", "create_bert",
]
