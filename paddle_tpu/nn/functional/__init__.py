"""paddle.nn.functional parity namespace."""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import (  # noqa: F401
    conv1d, conv2d, conv3d, conv1d_transpose, conv2d_transpose, conv3d_transpose,
)
from .pooling import (  # noqa: F401
    max_pool1d, max_pool2d, max_pool3d, avg_pool1d, avg_pool2d, avg_pool3d,
    adaptive_avg_pool1d, adaptive_avg_pool2d, adaptive_avg_pool3d,
    adaptive_max_pool1d, adaptive_max_pool2d, adaptive_max_pool3d,
    max_unpool1d, max_unpool2d, max_unpool3d,
)
from .norm import (  # noqa: F401
    batch_norm, layer_norm, instance_norm, group_norm, local_response_norm, rms_norm,
)
from .loss import (  # noqa: F401
    cross_entropy, softmax_with_cross_entropy, mse_loss, l1_loss, smooth_l1_loss,
    nll_loss, binary_cross_entropy, binary_cross_entropy_with_logits, kl_div,
    margin_ranking_loss, hinge_embedding_loss, cosine_embedding_loss,
    triplet_margin_loss, square_error_cost, sigmoid_focal_loss, ctc_loss,
    margin_cross_entropy, dice_loss, log_loss, npair_loss, hsigmoid_loss,
)
from .attention import (  # noqa: F401
    latent_attention_decode, latent_attention_prompt, latent_page_write,
    rotary_embedding, scaled_dot_product_attention, yarn_inv_freq,
    yarn_mscale,
)
from .vision import grid_sample, affine_grid, temporal_shift  # noqa: F401
