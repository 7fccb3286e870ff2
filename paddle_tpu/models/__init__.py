"""Model zoo covering the baseline configs (BASELINE.md):
LeNet (1), ResNet-50 (2), ERNIE/BERT-base (3), PP-YOLOE (4),
ERNIE-10B / GPT hybrid-parallel (5)."""
from .lenet import LeNet  # noqa: F401
from .resnet import (  # noqa: F401
    BasicBlock, BottleneckBlock, ResNet, ResNeXt, resnet18, resnet34,
    resnet50, resnet101, resnet152, wide_resnet50_2, wide_resnet101_2,
    resnext50_32x4d, resnext50_64x4d, resnext101_32x4d, resnext101_64x4d,
    resnext152_32x4d, resnext152_64x4d,
)
from .vgg import VGG, vgg11, vgg13, vgg16, vgg19  # noqa: F401
from .mobilenet import MobileNetV1, MobileNetV2, mobilenet_v1, mobilenet_v2  # noqa: F401
from .ernie import (  # noqa: F401
    ErnieForPretraining, ErnieForSequenceClassification, ErnieModel, bert_base,
    bert_large, ernie_base, ernie_large, ernie_titan_10b,
)
from .gpt import (  # noqa: F401
    GPTForCausalLM, GPTModel, GPTPretrainingCriterion, gpt2_medium, gpt2_small,
    gpt_10b, gpt_pipeline_layer,
)
from .brumby import BrumbyForCausalLM, BrumbyModel  # noqa: F401
from .ling import LingForCausalLM, LingModel  # noqa: F401
from .dots import DotsForCausalLM, DotsModel  # noqa: F401
from .nemotron import NemotronHForCausalLM, NemotronHModel  # noqa: F401
from .lfm2 import Lfm2MoeForCausalLM, Lfm2MoeModel  # noqa: F401
from .yoloe import PPYOLOE, ppyoloe_l, ppyoloe_m, ppyoloe_s  # noqa: F401
from .small_nets import (  # noqa: F401
    AlexNet, DenseNet, GoogLeNet, InceptionV3, ShuffleNetV2, SqueezeNet,
    alexnet, densenet121, densenet161, densenet169, densenet201, densenet264,
    googlenet, inception_v3, shufflenet_v2_x0_25, shufflenet_v2_x0_33,
    shufflenet_v2_x0_5, shufflenet_v2_x1_0, shufflenet_v2_x1_5,
    shufflenet_v2_x2_0, shufflenet_v2_swish, squeezenet1_0, squeezenet1_1,
)
from .pp_ocr import PPOCRRec, pp_ocrv3_rec  # noqa: F401
