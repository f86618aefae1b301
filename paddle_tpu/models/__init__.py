"""Model zoo — the acceptance workloads from BASELINE.json (MNIST LeNet,
ResNet, seq2seq attention NMT, sequence tagging, CTR) built on paddle_tpu.nn."""

from .conv_moe import ConvMoEBlock, ConvMoELM
from .ctr import CTR_SHARDING_RULES, SparseLR, WideDeepCTR
from .gan import Discriminator, Generator, gan_step_fn
from .latent_moe import LatentMoEBlock, LatentMoELM, ShortcutMoEBlock
from .image_zoo import AlexNet, GoogLeNet, VGG, vgg16, vgg19
from .mnist import LeNet, MnistMLP
from .resnet import (ResNet, resnet18, resnet34, resnet50, resnet101,
                     resnet152, resnet_cifar)
from .seq2seq import Seq2SeqAttention
from .ssd import SSDHead
from .vae import VAE, elbo_loss
from .tagging import LinearCrfTagger, RnnCrfTagger
from .text_cls import LSTMTextClassifier
from .traffic import TrafficPredictor
from .transformer import TransformerBlock, TransformerLM
from .window_moe import WindowMoEBlock, WindowMoELM
