from distributeddataparallel_tpu.ops.losses import (  # noqa: F401
    cross_entropy_loss,
    accuracy,
    lm_cross_entropy,
    multi_token_cross_entropy,
    per_example_accuracy,
    per_example_cross_entropy,
)
from distributeddataparallel_tpu.ops.preprocess import (  # noqa: F401
    normalize_u8_images,
)
from distributeddataparallel_tpu.ops.attention import (  # noqa: F401
    attention,
    dot_product_attention,
    apply_rope,
    rope_frequencies,
)
