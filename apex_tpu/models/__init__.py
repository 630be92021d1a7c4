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
from .zaya import ZayaLM  # noqa: F401


def build_lm(config: dict, **overrides):
    """The language model a published ``config.json`` describes, by its
    ``model_type``: ``gpt2`` -> :class:`TransformerLM` (``n_embd``,
    ``n_layer``, ``n_head``, ``n_positions``, ``vocab_size``), ``zaya`` ->
    :class:`ZayaLM` (``hidden_size``, ``num_hidden_layers``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``num_experts``, ``moe_intermediate_size``, ``router_hidden_size``,
    ``cca_time0/1``, ``rope_parameters``, ``partial_rotary_factor``,
    ``rms_norm_eps``, ``max_position_embeddings``, ``vocab_size``).
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
    raise ValueError(f"build_lm: no model for model_type {kind!r} "
                     "(have: 'gpt2', 'zaya')")

__all__ = [
    "BasicBlock", "Bottleneck", "ResNet", "ResNet18", "ResNet34", "ResNet50",
    "ResNet101", "ResNet152", "create_model",
    "TransformerLM", "TransformerBlock", "create_lm", "ZayaLM", "build_lm",
    "BertConfig", "BertModel", "BertForPreTraining", "create_bert",
]
