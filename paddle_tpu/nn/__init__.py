"""Layer/Module API — dygraph parity, functional core.

Ref: /root/reference/python/paddle/fluid/dygraph/ (layers.py Layer,
nn.py modules). See nn/module.py for the programming model.
"""

from paddle_tpu.nn.module import Module, ModuleList, Sequential
from paddle_tpu.nn.layers import (
    FC,
    NCE,
    BatchNorm,
    BilinearTensorProduct,
    Conv2D,
    Conv2DTranspose,
    Conv3D,
    Dropout,
    Embedding,
    GRU,
    GroupNorm,
    GRUUnit,
    GroupedQueryAttention,
    LSTM,
    LayerNorm,
    Linear,
    MultiHeadAttention,
    Pool2D,
    PRelu,
    RMSNorm,
    RowConv,
    SequenceConv,
    SpectralNorm,
    SyncBatchNorm,
    TreeConv,
    fused_ffn,
    tied_vocab_head,
)

from paddle_tpu.nn.heads import MultiBoxHead
from paddle_tpu.nn.mamba import MambaMixer
from paddle_tpu.nn.scan import ScanLayers
from paddle_tpu.nn.moe import HeldExperts, MoE, top_k_gating
from paddle_tpu.nn.rnn import (RNN, BeamSearchDecoder, Decoder, GRUCell,
                               LSTMCell, RNNCell, dynamic_decode)

Layer = Module  # reference naming alias (dygraph.Layer)
