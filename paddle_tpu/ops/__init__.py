"""Op library: importing this package registers every op.

The TPU-native analog of the reference's ~150-op ``paddle/operators``
directory (SURVEY N2/A.1): one registry, each op a pure JAX function.
"""

from . import (  # noqa: F401
    math_ops,
    activation_ops,
    tensor_ops,
    nn_ops,
    loss_ops,
    optimizer_ops,
    random_ops,
    metric_ops,
    sequence_ops,
    nested_ops,
    seq2seq_ops,
    control_flow_ops,
    attention_ops,
    generation_ops,
    moe_ops,
    mla_ops,
    eva_ops,
    ssm_ops,
    decoding_ops,
    crf_ctc_ops,
    beam_search_ops,
    sparse_ops,
    detection_ops,
    misc_ops,
    legacy_tail_ops,
    pallas_conv_bn,
)
