"""Layer library: core layers, activations, costs, sequence ops, recurrent nets,
attention — the TPU-native successor of paddle/gserver/layers (+ fluid operators)."""

from . import activations, autotune, costs, ctc, detection, moe, sequence_ops
from .fused_ln import fused_ln_matmul, ln_matmul_reference
from .attention import (AdditiveAttention, DotProductAttention,
                        MultiHeadAttention)
from .crf import CRF, crf_decode, crf_log_likelihood
from .moe import HeldExpertsFFN, MoEFFN, moe_sharding_rules
from .detection import (DetectionOutput, MultiBoxLoss, ROIPool,
                        decode_boxes, encode_boxes, iou_matrix, nms,
                        prior_box)
from .ctc import ctc_greedy_decode, ctc_loss
from .layers import *  # noqa: F401,F403
from .layers import __all__ as _layers_all
from .recurrent import (RNN, BiRNN, GatedShortConv, GRUCell,
                        HierarchicalRNN, LSTMCell, MDLstm, SimpleRNNCell)

__all__ = list(_layers_all) + [
    "activations", "costs", "sequence_ops", "RNN", "BiRNN", "GatedShortConv",
    "GRUCell",
    "HierarchicalRNN", "LSTMCell", "MDLstm", "SimpleRNNCell", "CRF", "crf_decode", "crf_log_likelihood",
    "ctc_loss", "ctc_greedy_decode", "AdditiveAttention", "DotProductAttention",
    "MultiHeadAttention", "detection", "DetectionOutput", "MultiBoxLoss",
    "ROIPool", "prior_box", "nms", "iou_matrix", "encode_boxes", "decode_boxes",
    "MoEFFN", "HeldExpertsFFN", "moe_sharding_rules", "moe",
    "autotune", "fused_ln_matmul", "ln_matmul_reference",
]
