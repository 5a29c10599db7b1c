"""`paddle.nn`-equivalent package (reference: python/paddle/nn/__init__.py).

Layer classes are dygraph modules over the eager jax engine; the same
`forward` traces under `paddle_tpu.jit.to_static` / `jax.jit` into one XLA
computation (the TPU replacement for the reference's dy2static AST
transpiler, SURVEY.md §7 step 8).
"""

from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer.activation import (ELU, GELU, SELU, Hardshrink, Hardsigmoid,
                               Hardswish, Hardtanh, LeakyReLU, LogSoftmax,
                               Maxout, Mish, PReLU, ReLU, ReLU6, Sigmoid,
                               Silu, Softmax, Softplus, Softshrink, Swish,
                               Tanh, Tanhshrink, ThresholdedReLU)
from .layer.common import (Bilinear, CosineSimilarity, Dropout, Dropout2D, SwitchMoE,
                           RoutedMoE,
                           Embedding, Flatten, Linear, Pad1D, Pad2D, Pad3D,
                           PixelShuffle, Upsample, UpsamplingBilinear2D,
                           UpsamplingNearest2D)
from .layer.container import LayerList, ParameterList, Sequential
from .layer.conv import Conv1D, Conv2D, Conv2DTranspose, Conv3D
from .layer.layers import Layer, Parameter
from .layer.loss import (BCELoss, BCEWithLogitsLoss, CrossEntropyLoss,
                         KLDivLoss, L1Loss, MarginRankingLoss, MSELoss,
                         NLLLoss, SmoothL1Loss)
from .layer.norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
                         GroupNorm, InstanceNorm1D, InstanceNorm2D,
                         InstanceNorm3D, LayerNorm, LocalResponseNorm, RMSNorm,
                         SpectralNorm, SyncBatchNorm)
from .layer.pooling import (AdaptiveAvgPool2D, AdaptiveMaxPool2D, AvgPool1D,
                            AvgPool2D, MaxPool1D, MaxPool2D)
from .layer.rnn import (RNN, BiRNN, GRU, GRUCell, LSTM, LSTMCell,
                        RNNCellBase, SimpleRNN, SimpleRNNCell)
from .layer.transformer import (GatedDeltaNet, GatedFFN,
                                GatedWindowAttention, GroupedQueryAttention,
                                KimiDeltaAttention, LatentAttention,
                                ShortConvSiLU, MultiHeadAttention, Transformer,
                                TransformerDecoder, TransformerDecoderLayer,
                                TransformerEncoder, TransformerEncoderLayer)

# 2.0 nn tail (reference nn/__init__.py uncommented DEFINE_ALIAS set)
from .layer import conv, loss  # noqa: F401 - submodule aliases
from .layer import vision  # noqa: F401
from .layer.extra_layers import (AdaptiveAvgPool1D, AdaptiveAvgPool3D,
                                 AdaptiveMaxPool1D, AdaptiveMaxPool3D,
                                 AlphaDropout, AvgPool3D,
                                 BilinearTensorProduct, CTCLoss,
                                 Conv1DTranspose, Conv3DTranspose,
                                 Dropout3D, HSigmoidLoss, LogSigmoid,
                                 MaxPool3D, PairwiseDistance, Pool2D,
                                 RowConv, Softsign)
from ..fluid.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                          ClipGradByValue)
from ..fluid.layers import clip, clip_by_norm  # noqa: F401
from .decode import BeamSearchDecoder, Decoder, dynamic_decode
