"""Model zoo (reference: BigDL models/ + example/, SURVEY.md §2.11)."""

from .alexnet import AlexNet
from .autoencoder import Autoencoder
from .inception import (Inception_Layer_v1, Inception_Layer_v2,
                        Inception_v1, Inception_v1_NoAuxClassifier,
                        Inception_v2, Inception_v2_NoAuxClassifier)
from .decode import beam_generate, cached_generate, init_kv_cache
from .deepseek import DeepSeekV2LM
from .jamba import JambaLM
from .lenet import LeNet5
from .mellum import MellumLM
from .nemotron import NemotronHLM
from .qwen3_next import Qwen3NextLM
from .resnet import ResNet, ShortcutType
from .rnn import PTBModel, SimpleRNN
from .textclassifier import TextClassifier
from .transformer_lm import (PositionalEmbedding, TransformerBlock,
                             TransformerLM)
from .treelstm_sentiment import TreeLSTMSentiment, encode_tree
from .vgg import Vgg_16, Vgg_19, VggForCifar10
from .vit import ViT
from .widedeep import WideDeep

__all__ = [
    "AlexNet", "Autoencoder", "DeepSeekV2LM", "Inception_Layer_v1", "Inception_Layer_v2",
    "Inception_v1", "Inception_v1_NoAuxClassifier", "Inception_v2",
    "Inception_v2_NoAuxClassifier", "JambaLM", "LeNet5", "MellumLM", "NemotronHLM", "PTBModel",
    "PositionalEmbedding", "Qwen3NextLM", "ResNet", "ShortcutType", "SimpleRNN",
    "TextClassifier", "TransformerBlock", "TransformerLM",
    "TreeLSTMSentiment", "beam_generate", "cached_generate",
    "encode_tree", "init_kv_cache",
    "Vgg_16", "Vgg_19", "VggForCifar10", "ViT", "WideDeep",
]
