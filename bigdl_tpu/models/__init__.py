"""Model zoo (reference bigdl/models/: lenet, vgg, inception, resnet, rnn,
autoencoder + example/loadmodel AlexNet)."""

from bigdl_tpu.models.lenet import lenet5
from bigdl_tpu.models.vgg import vgg_for_cifar10, vgg16, vgg19
from bigdl_tpu.models.resnet import (
    resnet, resnet_cifar, resnet50, basic_block, bottleneck_block,
)
from bigdl_tpu.models.inception import (
    inception_v1, inception_v1_no_aux, inception_v2, inception_module,
)
from bigdl_tpu.models.alexnet import alexnet
from bigdl_tpu.models.autoencoder import autoencoder
from bigdl_tpu.models.rnn import (
    simple_rnn, lstm_classifier, birnn_classifier, text_cnn,
)
from bigdl_tpu.models.vit import ViT, vit, vit_b16, vit_s16
from bigdl_tpu.models.transformer_lm import (
    TransformerLM, transformer_lm, packed_lm_targets, PackedNLLCriterion,
)
from bigdl_tpu.models.sambay_lm import SambaYLM, sambay_lm
from bigdl_tpu.models.hybrid_moe_lm import (
    HybridMoELM, hybrid_moe_lm, smallthinker, solar_open2,
)
