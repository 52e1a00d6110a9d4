"""Model × dataset factory — parity with the reference's ``create_model``
switch (``fedml_experiments/distributed/fedavg/main_fedavg.py:224-259``).

The reference pairs a model name with a dataset to pick both the
architecture and the trainer flavor (classification / next-word prediction /
tag prediction — FedAvgAPI.py:33-39).  Here the same switch returns a
``Workload`` (model + loss + metrics bundled), so every runner downstream is
algorithm-generic.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from fedml_tpu.data.stacking import FederatedData
from fedml_tpu.models import (
    CNNDropOut, CNNOriginalFedAvg, LogisticRegression, RNNOriginalFedAvg,
    RNNStackOverflow, TransformerLM, efficientnet, mobilenet, mobilenet_v3,
    resnet18_gn, resnet56, resnet110, vgg11, vgg13, vgg16)
from fedml_tpu.trainer.workload import (
    ClassificationWorkload, NWPWorkload, TagPredictionWorkload, Workload)

# next-word/char-prediction datasets -> NWP trainer flavor
_NWP_DATASETS = {"shakespeare", "fed_shakespeare", "stackoverflow_nwp",
                 "token_shards"}


def arch_of(model_config: str):
    """The arch a ``--model_config`` file describes, by its ``model_type``
    (a file that states none is read as ``glm4_moe_lite``, the one arch
    there was before the key was read)."""
    import json
    from fedml_tpu.models.indexed_attention import IndexedGQAArch
    from fedml_tpu.models.transformer import LatentMoEArch
    from fedml_tpu.models.window_attention import WindowGQAArch
    archs = {"glm4_moe_lite": LatentMoEArch, "KeyeVL2": IndexedGQAArch,
             "laguna": WindowGQAArch}
    with open(model_config) as f:
        keys = json.load(f)
    kind = keys.get("model_type", "glm4_moe_lite")
    if kind not in archs:
        raise ValueError(f"--model_config {model_config}: no arch is built "
                         f"for model_type {kind!r}; have {sorted(archs)}")
    return archs[kind].from_dict(keys)


def create_workload(model_name: str, dataset: str, class_num: int,
                    sample_shape: Sequence[int],
                    compute_dtype: str = "",
                    attn_block_size: int = 0,
                    attn_flash: bool = False,
                    moe_experts: int = 0,
                    model_config: str = "") -> Workload:
    """main_fedavg.py:224-259 switch, flax edition.

    ``compute_dtype="bfloat16"`` enables MXU-native mixed precision on the
    classification workloads (f32 master params, bf16 model compute).
    ``attn_block_size`` > 0 gives the transformer flash-style kv blocking
    (O(T*block) attention memory) for long-context train/eval;
    ``attn_flash`` swaps in the TPU pallas flash kernel instead.
    ``model_config`` names a JSON file of a published architecture's keys:
    the transformer is then built from them as the arch the file's
    ``model_type`` names (`arch_of`), every width the file's, over a
    next-token dataset whose vocabulary is the file's ``vocab_held``."""
    import jax.numpy as jnp
    dtype = jnp.dtype(compute_dtype) if compute_dtype else None
    if (attn_block_size or attn_flash or moe_experts) \
            and model_name != "transformer":
        raise ValueError("--attn_block_size/--attn_flash/--moe_experts "
                         "only apply to --model transformer")
    if attn_block_size and attn_flash:
        raise ValueError("--attn_block_size and --attn_flash are mutually "
                         "exclusive attention backends; pick one")
    arch = None
    if model_config:
        if model_name != "transformer" or dataset not in _NWP_DATASETS:
            raise ValueError("--model_config describes --model transformer "
                             "over a next-token dataset "
                             f"({sorted(_NWP_DATASETS)})")
        if attn_flash or moe_experts:
            raise ValueError("--attn_flash/--moe_experts are the "
                             "learned-position transformer's; a "
                             "--model_config states its own attention and "
                             "experts")
        arch = arch_of(model_config)
        if arch.vocab_held != class_num:
            raise ValueError(
                f"--model_config holds {arch.vocab_held} rows of the "
                f"vocabulary and --dataset {dataset} draws its ids from "
                f"{class_num}")
    if attn_flash:
        # refuse HERE, at config time, what the kernel would otherwise
        # refuse at trace time inside the first training jit
        import jax
        from fedml_tpu.models.transformer import FLASH_BLOCK
        seq_len = int(sample_shape[0])
        if jax.default_backend() != "tpu":
            raise ValueError(
                f"--attn_flash is JAX's TPU flash-attention kernel and "
                f"this run resolved to the {jax.default_backend()!r} "
                f"backend; use --attn_block_size instead")
        if seq_len % FLASH_BLOCK:
            raise ValueError(
                f"--attn_flash tiles the sequence in blocks of "
                f"{FLASH_BLOCK} and --dataset {dataset} has windows of "
                f"{seq_len} tokens; use --attn_block_size instead")
    if dtype is not None and dataset == "stackoverflow_lr":
        raise ValueError(
            f"--compute_dtype is not wired into the tag-prediction "
            f"workload; dataset {dataset!r} would silently ignore it")
    if dataset in _NWP_DATASETS:
        if model_name == "transformer":
            # the attention member of the NLP family (no reference analog —
            # its zoo stops at LSTMs, rnn.py:18-22); per-position logits,
            # same NWPWorkload contract, ring-attention capable
            model = TransformerLM(vocab_size=class_num, dtype=dtype,
                                  block_size=attn_block_size or None,
                                  use_flash=attn_flash,
                                  moe_experts=moe_experts, arch=arch)
        elif dataset == "stackoverflow_nwp":
            model = RNNStackOverflow(dtype=dtype)          # rnn.py:39-70
        else:
            model = RNNOriginalFedAvg(vocab_size=class_num,
                                      dtype=dtype)          # rnn.py:4-36
        return NWPWorkload(model, compute_dtype=dtype)
    if dataset == "stackoverflow_lr":
        model = LogisticRegression(int(np.prod(sample_shape)), class_num)
        return TagPredictionWorkload(model)

    input_dim = int(np.prod(sample_shape))
    small = class_num <= 10
    factories = {
        "lr": lambda: LogisticRegression(input_dim, class_num),
        "cnn": lambda: CNNDropOut(only_digits=small),          # Reddi'20
        "cnn_fedavg": lambda: CNNOriginalFedAvg(only_digits=small),
        "resnet56": lambda: resnet56(class_num),
        "resnet110": lambda: resnet110(class_num),
        "resnet18_gn": lambda: resnet18_gn(class_num),
        "mobilenet": lambda: mobilenet(num_classes=class_num),
        "mobilenet_v3": lambda: mobilenet_v3(num_classes=class_num),
        "efficientnet": lambda: efficientnet("b0", num_classes=class_num),
        "vgg11": lambda: vgg11(num_classes=class_num),
        "vgg13": lambda: vgg13(num_classes=class_num),
        "vgg16": lambda: vgg16(num_classes=class_num),
    }
    if model_name not in factories:
        raise KeyError(f"unknown model {model_name!r}; "
                       f"have {sorted(factories)}")
    # grad-clip 1.0 parity with MyModelTrainer (classification only,
    # my_model_trainer_classification.py:44)
    return ClassificationWorkload(factories[model_name](),
                                  num_classes=class_num, grad_clip_norm=1.0,
                                  compute_dtype=dtype)


def sample_shape_of(data: FederatedData) -> tuple:
    return tuple(data.train["x"].shape[3:])
