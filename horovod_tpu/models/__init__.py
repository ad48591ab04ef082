"""Model zoo backing the examples and benchmarks.

The reference ships no model library — its acceptance surface is the
`examples/` scripts (ResNet-50 via `keras.applications`, MNIST convnets,
word2vec; /root/reference/examples/).  Those architectures live here as
first-class flax modules so the examples, the benchmark harness, and the
driver's graft entry all share one TPU-tuned implementation.
"""

from horovod_tpu.models.mnist import MnistCNN  # noqa: F401
from horovod_tpu.models.norm import (  # noqa: F401
    BatchStatsNorm,
    ema_batch_stats,
)
from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.delta import DeltaConfig, DeltaMixer  # noqa: F401
from horovod_tpu.models.ssm import Mamba2Config, Mamba2Mixer  # noqa: F401
from horovod_tpu.models.transformer import (  # noqa: F401
    DecodeContext,
    IndexerConfig,
    LatentAttention,
    LatentConfig,
    MoEConfig,
    RopeScaling,
    TransformerLM,
    indexer_loss,
    log_exit_distribution,
    looped_exit_loss,
    masked_diffusion_loss,
    moe_next_token_loss,
    mtp_next_token_loss,
    next_token_loss,
    record_attention_blocks,
    record_attention_selection,
    record_delta_steps,
    record_exit_distribution,
    record_expert_rows,
    record_mtp_losses,
    record_ssm_carry,
    router_losses,
)
from horovod_tpu.models.vgg import VGG11, VGG16, VGG19  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
