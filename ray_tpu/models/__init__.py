"""TPU-native model zoo (the role torch models play inside the
reference's Train/Serve/RLlib workers).

Training symbols load lazily (PEP 562) so inference-only paths don't
pull in optax.
"""

from ray_tpu.models.transformer import (  # noqa: F401
    EvaSizes,
    KdaSizes,
    LayerSpec,
    MlaSizes,
    SparseSizes,
    TransformerConfig,
    config_from_hf,
    forward,
    forward_with_stats,
    init_params,
    loss_fn,
    param_specs,
    record_sparse_visits,
)
from ray_tpu.models.vit import (  # noqa: F401
    ViTConfig,
    init_vit_params,
    vit_forward,
    vit_loss_fn,
    vit_param_specs,
)

_TRAINING = ("TrainState", "init_state", "make_optimizer",
             "make_train_step", "state_specs")

__all__ = ["EvaSizes", "KdaSizes", "LayerSpec", "MlaSizes", "SparseSizes",
           "TransformerConfig",
           "ViTConfig", "config_from_hf", "forward", "forward_with_stats",
           "init_params", "init_vit_params", "loss_fn", "param_specs",
           "record_sparse_visits", "vit_forward", "vit_loss_fn",
           "vit_param_specs", *_TRAINING]


def __getattr__(name):
    if name in _TRAINING:
        from ray_tpu.models import training
        return getattr(training, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
