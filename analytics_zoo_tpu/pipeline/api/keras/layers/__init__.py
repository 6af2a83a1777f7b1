from ..engine.base import Input, InputLayer, KerasLayer
from .core import (AddConstant, Activation, BinaryThreshold, CAdd, CMul,
                   Dense, Dropout, Exp, ExpandDim, Flatten, GaussianDropout,
                   GaussianNoise, GaussianSampler, HardShrink, HardTanh,
                   Highway, Identity, Log, Masking, Max, MaxoutDense, Mul,
                   MulConstant, Narrow, Negative, Permute, Power,
                   RepeatVector, Reshape, ResizeBilinear, Scale, Select,
                   SoftShrink, SpatialDropout1D, SpatialDropout2D,
                   SpatialDropout3D, SplitTensor, Sqrt, Square, Squeeze,
                   Threshold, Expand, GetShape, SelectTable, SparseDense)
from .embeddings import Embedding, SparseEmbedding, WordEmbedding
from .merge import (Add, Average, Concatenate, Maximum, Merge, Multiply,
                    merge)
from .normalization import (BatchNormalization, LayerNorm, LRN2D,
                            WithinChannelLRN2D)
from .convolutional import (AtrousConvolution1D, AtrousConvolution2D,
                            Convolution1D, Convolution2D, Convolution3D,
                            Cropping1D, Cropping2D, Cropping3D,
                            Deconvolution2D, LocallyConnected1D,
                            LocallyConnected2D, SeparableConvolution2D,
                            ShareConvolution2D, UpSampling1D, UpSampling2D,
                            UpSampling3D, ZeroPadding1D, ZeroPadding2D,
                            ZeroPadding3D)
from .pooling import (AveragePooling1D, AveragePooling2D, AveragePooling3D,
                      GlobalAveragePooling1D, GlobalAveragePooling2D,
                      GlobalAveragePooling3D, GlobalMaxPooling1D,
                      GlobalMaxPooling2D, GlobalMaxPooling3D, MaxPooling1D,
                      MaxPooling2D, MaxPooling3D)
from .recurrent import (GRU, LSTM, ConvLSTM2D, ConvLSTM3D, SimpleRNN)
from .wrappers import Bidirectional, KerasLayerWrapper, TimeDistributed
from .advanced_activations import (ELU, LeakyReLU, PReLU, RReLU, Softmax,
                                   SReLU, ThresholdedReLU)
from .moe import SparseMoE
from .hybrid_decoder import (GatedAttention, GatedDeltaNet, GatedMLP,
                             HeldExpertsMoE, HybridDecoder,
                             KimiDeltaAttention, LatentAttention, LMHeadLoss)
from .crf import CRF

# Convenience aliases matching Keras-2-style names used around the reference
Conv1D = Convolution1D
Conv2D = Convolution2D
Conv3D = Convolution3D
