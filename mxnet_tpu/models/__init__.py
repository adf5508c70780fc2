"""Model zoo: symbols for the BASELINE.json workloads
(reference example/image-classification/symbol_*.py, example/rnn)."""

from .lenet import get_symbol as lenet
from .mlp import get_symbol as mlp
from .resnet import get_symbol as resnet
from .lstm import lstm_unroll, lstm_cell, LSTMState, LSTMParam
from .ssd import get_symbol as ssd
from .inception import inception_bn, inception_bn_small, googlenet
from .vgg import vgg, alexnet
from .transformer import gpt
from .generate import gpt_decode_config, gpt_generate
from .hybrid import hybrid_decoder
from .moe import moe_decoder
from .branch import branch_decoder

__all__ = ["lenet", "mlp", "resnet", "lstm_unroll", "lstm_cell",
           "LSTMState", "LSTMParam", "ssd",
           "inception_bn", "inception_bn_small", "googlenet", "vgg", "alexnet",
           "gpt", "gpt_generate", "gpt_decode_config", "hybrid_decoder",
           "moe_decoder", "branch_decoder"]
