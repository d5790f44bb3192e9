"""Model zoo (reference: benchmark/paddle configs + book models)."""

from .image import (  # noqa: F401
    alexnet,
    googlenet,
    lenet,
    resnet_cifar10,
    resnet_imagenet,
    smallnet,
    vgg,
)
from .seq2seq import seq2seq_attention, seq2seq_beam_decode  # noqa: F401
from .text import lstm_benchmark_net, stacked_lstm_net, word2vec_net  # noqa: F401
from .transformer import transformer_lm  # noqa: F401
from .olmoe import olmoe_lm  # noqa: F401
from .nemotron_h import nemotron_h_lm  # noqa: F401
from .glm_moe import glm_moe_lm  # noqa: F401
from .afmoe import afmoe_lm  # noqa: F401
from .lfm2_moe import lfm2_moe_lm  # noqa: F401
from .looped import looped_lm  # noqa: F401
from .phi4flash import phi4flash_layer_kinds, phi4flash_lm  # noqa: F401
from .keye_vl import keye_lm  # noqa: F401
