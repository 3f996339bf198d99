"""bigdl_tpu.nn — the Torch-style layer library, rebuilt TPU-native.

Reference inventory: BigDL `nn/` (151 files, 26,212 LoC — SURVEY.md §2.3).
"""

from .module import Module, Container, Criterion
from .initialization import (Zeros, Ones, ConstInitMethod, RandomUniform,
                             RandomNormal, Xavier, MsraFiller, BilinearFiller)
from .containers import (Sequential, TiedSequential, Concat, ConcatTable,
                         ParallelTable, MapTable, Identity, Echo, Bottle)
from .graph import Graph, Input, ModuleNode
from .activation import (ReLU, ReLU6, PReLU, RReLU, LeakyReLU, ELU, GELU,
                         Tanh, TanhShrink, Sigmoid, SoftMax, SoftMin,
                         SoftPlus, SoftSign, SoftShrink, HardShrink, HardTanh,
                         Threshold, LogSoftMax, LogSigmoid, SiLU)
from .linear import (Linear, Bilinear, CMul, CAdd, Mul, Add, MulConstant,
                     AddConstant)
from .conv import (SpatialConvolution, SpatialDilatedConvolution,
                   SpatialFullConvolution, TemporalConvolution,
                   VolumetricConvolution, SpatialShareConvolution,
                   SpatialConvolutionMap)
from .pooling import (SpatialMaxPooling, SpatialAveragePooling,
                      VolumetricMaxPooling, RoiPooling)
from .detection import Nms
from .tree import TreeLSTM, BinaryTreeLSTM
from .normalization import (BatchNormalization, SpatialBatchNormalization,
                            LayerNorm, Normalize, RMSNorm, SpatialCrossMapLRN,
                            SpatialWithinChannelLRN,
                            SpatialSubtractiveNormalization,
                            SpatialDivisiveNormalization,
                            SpatialContrastiveNormalization)
from .dropout import Dropout, GradientReversal
from .embedding import LookupTable
from .shape import (Reshape, InferReshape, View, Transpose, Replicate, Squeeze,
                    Unsqueeze, Select, Narrow, Index, MaskedSelect, Reverse,
                    Padding, SpatialZeroPadding, Contiguous)
from .math_ops import (Power, Sqrt, Square, Clamp, Max, Min, Mean, Sum, Exp,
                       Log, Abs, Scale, MM, MV, Cosine, Euclidean, DotProduct,
                       PairwiseDistance, CosineDistance)
from .table_ops import (CAddTable, CSubTable, CMulTable, CDivTable, CMaxTable,
                        CMinTable, JoinTable, SplitTable, NarrowTable,
                        FlattenTable, SelectTable, MixtureTable, Pack)
from .recurrent import (Cell, RnnCell, LSTM, LSTMPeephole, GRU,
                        ConvLSTMPeephole, ConvLSTMPeephole3D, Recurrent,
                        TimeDistributed,
                        BiRecurrent)
from .criterion import (
    AbsCriterion, BCECriterion, ClassNLLCriterion, ClassSimplexCriterion,
    CosineDistanceCriterion, CosineEmbeddingCriterion, CrossEntropyCriterion,
    DiceCoefficientCriterion, DistKLDivCriterion, HingeEmbeddingCriterion,
    L1Cost, L1HingeEmbeddingCriterion, L1Penalty, MarginCriterion,
    MarginRankingCriterion, MSECriterion, MultiCriterion,
    MultiLabelMarginCriterion, MultiLabelSoftMarginCriterion,
    MultiMarginCriterion, ParallelCriterion, SmoothL1Criterion,
    SmoothL1CriterionWithWeights, SoftMarginCriterion, SoftmaxWithCriterion,
    TimeDistributedCriterion)
from .attention import LatentAttention, MultiHeadAttention
from .window_attention import RotaryAttention, WindowAttention
from .mamba import Mamba2Mixer, MambaMixer
from .deltanet import GatedDeltaNet
