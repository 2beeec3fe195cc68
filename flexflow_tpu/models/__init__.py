from .alexnet import build_alexnet
from .candle_uno import build_candle_uno
from .dlrm import build_dlrm
from .inception import build_inception_v3
from .resnet import build_resnet50
from .nmt import build_lstm_lm, build_nmt
from .transformer import build_transformer, build_transformer_lm
from .decoder_lm import build_decoder_lm
