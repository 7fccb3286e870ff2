from .layers import Layer  # noqa: F401
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .activation import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .container import Sequential, LayerList, LayerDict, ParameterList  # noqa: F401
from .rnn import *  # noqa: F401,F403
from .routed_experts import RoutedExperts  # noqa: F401
from .transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
