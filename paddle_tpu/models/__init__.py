"""Model zoo covering the BASELINE.json configs: LeNet (MNIST), ResNet-50,
BERT-base, Transformer-big, DeepFM (reference model sources:
``python/paddle/fluid/tests/book/`` + PaddleCV/PaddleNLP recipes)."""

from paddle_tpu.models.lenet import LeNet
from paddle_tpu.models.bert import (BertConfig, BertModel, BertForPretraining)
from paddle_tpu.models.resnet import ResNet, ResNet50
from paddle_tpu.models.deepfm import DeepFM
from paddle_tpu.models.transformer import Transformer, TransformerConfig
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.models.book import (LinearRegression, MachineTranslation,
                                    RNNLanguageModel,
                                    RecommenderSystem, SentimentCNN,
                                    SentimentLSTM,
                                    SkipGramNS, Word2Vec)
from paddle_tpu.models.mobilenet import MobileNetV1, MobileNetV2
from paddle_tpu.models.vgg import VGG, VGG16
from paddle_tpu.models.se_resnext import SEResNeXt, SEResNeXt50
from paddle_tpu.models.ssd import SSD, SSDConfig
from paddle_tpu.models.faster_rcnn import (FasterRCNN, FasterRCNNConfig,
                                            MaskRCNN)
from paddle_tpu.models.legacy_cv import (AlexNet, DarkNet53,
                                         DenseNet121, GoogLeNet,
                                         ShuffleNetV2, SqueezeNet)
from paddle_tpu.models.video import C3D, TSN
from paddle_tpu.models.yolov3 import YOLOv3, YOLOv3Config
from paddle_tpu.models.ocr import CRNN
from paddle_tpu.models.gan import (DCGANDiscriminator, DCGANGenerator,
                                   gan_step)
from paddle_tpu.models.sparse_moe_lm import SparseMoELM, SparseMoELMConfig
from paddle_tpu.models.hybrid_ssm_lm import HybridSSMLM, HybridSSMLMConfig
from paddle_tpu.models.latent_conv_moe_lm import (LatentConvMoELM,
                                                  LatentConvMoELMConfig)
from paddle_tpu.models.window_moe_lm import WindowMoELM, WindowMoELMConfig
from paddle_tpu.models.mla_moe_lm import MLAMoELM, MLAMoELMConfig
from paddle_tpu.models.gated_delta_moe_lm import (GatedDeltaMoELM,
                                                  GatedDeltaMoELMConfig)

__all__ = ["LeNet", "BertConfig", "BertModel", "BertForPretraining",
           "ResNet", "ResNet50", "DeepFM", "Transformer",
           "TransformerConfig", "GPT", "GPTConfig", "LinearRegression",
           "MachineTranslation", "RNNLanguageModel", "SentimentCNN", "SentimentLSTM", "SkipGramNS", "Word2Vec", "RecommenderSystem",
           "MobileNetV1", "MobileNetV2", "VGG", "VGG16", "SEResNeXt",
           "SEResNeXt50", "AlexNet", "DarkNet53", "DenseNet121", "GoogLeNet", "ShuffleNetV2", "SqueezeNet", "SSD", "SSDConfig", "FasterRCNN", "FasterRCNNConfig", "MaskRCNN", "C3D", "TSN", "YOLOv3", "YOLOv3Config", "CRNN", "DCGANGenerator", "DCGANDiscriminator", "gan_step",
           "SparseMoELM", "SparseMoELMConfig", "HybridSSMLM",
           "HybridSSMLMConfig", "LatentConvMoELM", "LatentConvMoELMConfig",
           "WindowMoELM", "WindowMoELMConfig", "MLAMoELM", "MLAMoELMConfig",
           "GatedDeltaMoELM", "GatedDeltaMoELMConfig"]
