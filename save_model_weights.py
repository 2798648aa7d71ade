"""Save model weights files (parity with /root/reference/save_model_weights.py).

The reference downloads checkpoints from the network (Google storage /
HF hub / torch hub). Under zero egress this script instead converts from a
local HF cache when available, or (with --random) generates randomly-
initialized weights in the exact on-disk format the loaders expect — useful
for benchmarking and for exercising the real weights-file code path offline.
"""
import argparse
import logging
import os
import sys

import numpy as np

from pipeedge_tpu.models import registry

logging.basicConfig(stream=sys.stdout, level=logging.INFO, format='%(message)s')
logger = logging.getLogger(__name__)


def _hf_config_for(cfg):
    """Build the matching HF config from our local TransformerConfig."""
    kwargs = dict(hidden_size=cfg.hidden_size,
                  num_hidden_layers=cfg.num_hidden_layers,
                  num_attention_heads=cfg.num_attention_heads,
                  intermediate_size=cfg.intermediate_size)
    if cfg.model_type in ("vit", "deit"):
        kwargs.update(image_size=cfg.image_size, patch_size=cfg.patch_size,
                      num_labels=max(cfg.num_labels, 2))
        if cfg.model_type == "vit":
            from transformers import ViTConfig
            return ViTConfig(**kwargs)
        from transformers import DeiTConfig
        return DeiTConfig(**kwargs)
    if cfg.model_type == "gpt2":
        from transformers import GPT2Config
        return GPT2Config(n_embd=cfg.hidden_size,
                          n_layer=cfg.num_hidden_layers,
                          n_head=cfg.num_attention_heads,
                          n_inner=cfg.intermediate_size,
                          vocab_size=cfg.vocab_size,
                          n_positions=cfg.max_position_embeddings)
    if cfg.model_type == "llama":
        common = dict(kwargs, num_key_value_heads=cfg.kv_heads,
                      vocab_size=cfg.vocab_size,
                      max_position_embeddings=cfg.max_position_embeddings,
                      rms_norm_eps=cfg.layer_norm_eps,
                      rope_theta=cfg.rope_theta,
                      tie_word_embeddings=False)
        if cfg.sliding_window:
            from transformers import MistralConfig
            return MistralConfig(**common,
                                 sliding_window=cfg.sliding_window)
        from transformers import LlamaConfig
        return LlamaConfig(**common, attention_bias=False, mlp_bias=False)
    from transformers import BertConfig
    return BertConfig(**kwargs, vocab_size=cfg.vocab_size,
                      max_position_embeddings=cfg.max_position_embeddings,
                      num_labels=max(cfg.num_labels, 2))


def _hf_model(model_name: str, cfg, random_init: bool):
    """Instantiate the HF torch model: pretrained if cached, else random."""
    import torch
    if cfg.model_type == "vit":
        from transformers import ViTForImageClassification as Cls
    elif cfg.model_type == "deit":
        from transformers import DeiTForImageClassificationWithTeacher as Cls
    elif cfg.model_type == "gpt2":
        from transformers import GPT2LMHeadModel as Cls
    elif cfg.model_type == "llama":
        if cfg.sliding_window:
            from transformers import MistralForCausalLM as Cls
        else:
            from transformers import LlamaForCausalLM as Cls
    elif cfg.num_labels > 0:
        from transformers import BertForSequenceClassification as Cls
    else:
        from transformers import BertModel as Cls
    if random_init:
        torch.manual_seed(0)
        return Cls(_hf_config_for(cfg))
    return Cls.from_pretrained(model_name)


def save_weights(model_name: str, model_file: str, random_init: bool = False) -> None:
    """Convert an HF model to the reference npz format for `model_name`."""
    entry = registry.get_model_entry(model_name)
    cfg = entry.config
    if cfg.n_experts:
        # synthetic MoE family: no pretrained checkpoint exists to convert
        if not random_init:
            raise ValueError(f"{model_name} is a synthetic MoE model with "
                             "no pretrained checkpoint; pass --random")
        np.savez(model_file, **entry.family.moe_state_dict(cfg))
        return
    model = _hf_model(model_name, cfg, random_init)
    state_dict = {k: v.numpy() for k, v in model.state_dict().items()}
    if cfg.model_type in ("vit", "deit"):
        weights = entry.family.hf_to_npz_weights(state_dict, cfg)
    else:
        weights = state_dict  # BERT/GPT-2 native format IS the HF state dict
    np.savez(model_file, **weights)


if __name__ == "__main__":
    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    parser = argparse.ArgumentParser(description="Save model weights files")
    parser.add_argument("-m", "--model-name", action='append',
                        choices=registry.get_model_names(),
                        help="Model name (default: all models)")
    parser.add_argument("--random", action='store_true',
                        help="generate randomly-initialized weights (offline)")
    parser.add_argument("-o", "--output-dir", default=".",
                        help="directory to write the npz files into")
    args = parser.parse_args()

    os.makedirs(args.output_dir, exist_ok=True)
    model_names = registry.get_model_names() if args.model_name is None \
        else args.model_name
    failed = []
    for name in model_names:
        model_file = os.path.join(
            args.output_dir, registry.get_model_default_weights_file(name))
        if os.path.exists(model_file):
            logger.info('%s: weights file already exists: %s', name, model_file)
            continue
        logger.info('%s: saving weights file: %s', name, model_file)
        try:
            save_weights(name, model_file, random_init=args.random)
        except Exception as exc:   # go on to the other models, then fail
            logger.error('%s: failed (%s); pass --random for offline weights',
                         name, exc)
            failed.append(name)
    if failed:
        sys.exit(f"no weights file for: {', '.join(failed)}")
