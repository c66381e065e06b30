"""Torch-style layer library (flat namespace, mirroring the reference's ``<dl>/nn/``)."""

from bigdl_tpu.nn.abstractnn import AbstractModule, Container, TensorModule
from bigdl_tpu.nn.attention import (CrossAttention, LatentAttention, MultiHeadAttention,
                                   rope_rotate, yarn_inv_freq, yarn_mscale)
from bigdl_tpu.nn.activation import (
    Abs, AddConstant, BinaryThreshold, Clamp, ELU, Exp, GELU, HardSigmoid, HardTanh,
    LeakyReLU, Log, LogSigmoid, LogSoftMax, MulConstant, Power, PReLU, ReLU, ReLU6,
    Sigmoid, SoftMax, SoftMin, SoftPlus, SoftSign, Sqrt, Square, SReLU, Swish,
    Tanh, TanhShrink,
)
from bigdl_tpu.nn.containers import (
    BifurcateSplitTable, Bottle, CAddTable, CAveTable, CDivTable, CMaxTable, CMinTable,
    CMulTable, CSubTable, Concat, ConcatTable, Echo, FlattenTable, Identity, JoinTable,
    MapTable, MaskedSelect, MixtureTable, NarrowTable, Pack, ParallelTable,
    Remat, SelectTable, Sequential,
)
from bigdl_tpu.nn.misc import (
    Bilinear, DotProduct, Euclidean, GaussianSampler, GradientReversal, HardShrink,
    Highway, L1Penalty, Max, Maxout, Mean, Min, MM, MV, Negative, PairwiseDistance,
    RReLU, ResizeBilinear, Scale, SoftShrink, SpatialUpSamplingBilinear,
    SpatialUpSamplingNearest, Sum, Threshold, UpSampling1D, UpSampling2D,
    UpSampling3D, Cropping2D, Cropping3D, ActivityRegularization,
    CrossProduct, NegativeEntropyPenalty, ImageNormalize,
)
from bigdl_tpu.nn.cosine import Cosine, CosineDistance
from bigdl_tpu.nn.convolution import (
    LocallyConnected1D, LocallyConnected2D, SpatialConvolution,
    SpatialConvolutionMap, SpatialDilatedConvolution, SpatialFullConvolution,
    SpatialSeparableConvolution, SpatialShareConvolution, TemporalConvolution,
)
from bigdl_tpu.nn.embedding import HashBucketEmbedding, LookupTable
from bigdl_tpu.nn.mamba import Mamba2Mixer
from bigdl_tpu.nn.graph import (
    Graph, Input, ModuleNode, StaticGraph, fuse_conv_bn,
)
from bigdl_tpu.nn.normalization import (
    Add, BatchNormalization, CAdd, CMul, Dropout, GaussianDropout, GaussianNoise,
    LayerNorm, Mul, Normalize, RMSNorm, SpatialBatchNormalization,
    SpatialContrastiveNormalization, SpatialCrossMapLRN,
    SpatialDivisiveNormalization, SpatialDropout1D, SpatialDropout2D,
    SpatialDropout3D, SpatialSubtractiveNormalization, SpatialWithinChannelLRN,
)
from bigdl_tpu.nn.recurrent import (
    BiRecurrent, Cell, ConvLSTMPeephole, ConvLSTMPeephole3D, GRU, LSTM,
    LSTMPeephole, Masking, MultiRNNCell, Recurrent, RecurrentDecoder, RnnCell,
    TimeDistributed,
)
from bigdl_tpu.nn.criterion import (
    AbsCriterion, AbstractCriterion, BCECriterion, BCECriterionWithLogits,
    ClassNLLCriterion, ClassSimplexCriterion, CosineDistanceCriterion,
    CosineEmbeddingCriterion, CosineProximityCriterion, CrossEntropyCriterion,
    DistKLDivCriterion, HingeEmbeddingCriterion, KullbackLeiblerDivergenceCriterion,
    L1Cost, L1HingeEmbeddingCriterion, MarginCriterion, MarginRankingCriterion,
    MeanAbsolutePercentageCriterion, MeanSquaredLogarithmicCriterion, MSECriterion,
    MultiCriterion, MultiLabelMarginCriterion, MultiLabelSoftMarginCriterion,
    MultiMarginCriterion, ParallelCriterion, PoissonCriterion, SmoothL1Criterion,
    SoftMarginCriterion, TimeDistributedCriterion,
    CategoricalCrossEntropy, DiceCoefficientCriterion, GaussianCriterion,
    KLDCriterion, SmoothL1CriterionWithWeights, SoftmaxWithCriterion,
    TimeDistributedMaskCriterion, TransformerCriterion,
)
from bigdl_tpu.nn.initialization import (
    BilinearFiller, ConstInitMethod, InitializationMethod, MsraFiller, Ones,
    RandomNormal, RandomUniform, Xavier, Zeros,
)
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.quantized import (
    QuantizedLinear, QuantizedSpatialConvolution,
    QuantizedSpatialDilatedConvolution, calibrate,
)
from bigdl_tpu.nn.sparse import (
    DenseToSparse, LookupTableSparse, SparseEmbeddingSum, SparseJoinTable,
    SparseLinear,
)
from bigdl_tpu.nn.roi import RoiPooling
from bigdl_tpu.nn.lora import LoRALinear, apply_lora, merge_lora
from bigdl_tpu.nn.fused_loss import (
    ChunkedSoftmaxCrossEntropy, FusedLMHead, chunked_softmax_xent,
)
from bigdl_tpu.nn.detection import (
    Anchor, DetectionOutputSSD, NormalizeScale, PriorBox, Proposal,
    decode_rcnn, decode_ssd, nms_mask, pairwise_iou,
)
from bigdl_tpu.nn.multibox import MultiBoxCriterion, encode_ssd, match_priors
from bigdl_tpu.nn.tree import BinaryTreeLSTM
from bigdl_tpu.nn.beam_search import SequenceBeamSearch, greedy_decode
from bigdl_tpu.nn.incremental import (
    assign_cache_slot, beam_generate, clear_decode_cache, generate,
    greedy_generate, install_decode_cache, reset_decode_slot)
from bigdl_tpu.nn.volumetric import (
    VolumetricAveragePooling, VolumetricConvolution, VolumetricFullConvolution,
    VolumetricMaxPooling,
)
from bigdl_tpu.nn.pooling import (
    SpatialAveragePooling, SpatialMaxPooling, TemporalAveragePooling,
    TemporalMaxPooling,
)
from bigdl_tpu.nn.transformer_layers import (
    Attention, ExpandSize, FeedForwardNetwork, LayerNormalization,
    TableOperation, Transformer,
)
from bigdl_tpu.nn.maskrcnn import (
    BoxHead, DetectionOutputFrcnn, FPN, MaskHead, Pooler, RegionProposal,
    RoiAlign,
)
from bigdl_tpu.nn.tf_utils import (
    Const, Fill, Shape, SplitAndSelect, StrideSlice,
)
from bigdl_tpu.nn.shape_ops import (
    Contiguous, Flatten, Index, InferReshape, Narrow, Padding, Replicate, Reshape,
    Reverse, Select, SpatialZeroPadding, SplitTable, Squeeze, Tile, Transpose,
    Unsqueeze, View,
)


def __getattr__(name):
    # FusedConvBNReLU subclasses Container, so kernels/conv_bn.py imports
    # this package — resolve the re-export lazily to break the cycle
    if name == "FusedConvBNReLU":
        from bigdl_tpu.kernels.conv_bn import FusedConvBNReLU
        return FusedConvBNReLU
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    # advertise the lazy export: the serialization registry scans dir(nn),
    # and a fresh process must resolve FusedConvBNReLU without having
    # imported kernels/conv_bn first
    return sorted(list(globals()) + ["FusedConvBNReLU"])
