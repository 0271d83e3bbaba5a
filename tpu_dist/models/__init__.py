"""Model layer: layer vocabulary, containers, reference model builders."""

from tpu_dist.models.layers import (
    Activation,
    AveragePooling2D,
    BatchNormalization,
    Block,
    Conv2D,
    Dense,
    Dropout,
    Flatten,
    GlobalAveragePooling2D,
    Layer,
    MaxPooling2D,
    ReLU,
    Residual,
)
from tpu_dist.models.model import Model, Sequential
from tpu_dist.models.serialize import load_model, save_model
from tpu_dist.models.transformer import (
    Embedding,
    LayerNormalization,
    MultiHeadAttention,
    PositionalEmbedding,
    TransformerBlock,
    build_transformer_lm,
)
from tpu_dist.models.hybrid import (
    DeltaAttention,
    GatedMLP,
    LatentAttention,
    RMSNorm,
    build_hybrid_lm,
)
from tpu_dist.models.cnn import build_and_compile_cnn_model, build_cnn_model
from tpu_dist.models.policy import compute_dtype, policy, set_policy
from tpu_dist.models.resnet import ResNet18, ResNet50

__all__ = [
    "Activation",
    "AveragePooling2D",
    "BatchNormalization",
    "Block",
    "Conv2D",
    "Dense",
    "Dropout",
    "Flatten",
    "GlobalAveragePooling2D",
    "Layer",
    "MaxPooling2D",
    "ReLU",
    "Residual",
    "Model",
    "Sequential",
    "load_model",
    "Embedding",
    "LayerNormalization",
    "MultiHeadAttention",
    "PositionalEmbedding",
    "TransformerBlock",
    "build_transformer_lm",
    "DeltaAttention",
    "GatedMLP",
    "LatentAttention",
    "RMSNorm",
    "build_hybrid_lm",
    "save_model",
    "ResNet18",
    "ResNet50",
    "build_and_compile_cnn_model",
    "build_cnn_model",
    "compute_dtype",
    "policy",
    "set_policy",
]
