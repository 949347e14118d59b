"""(backbone, top_head) of the C4 graph — ResNet only: the benchmark's
configurations are ResNet-101 C4 and ResNet-101-FPN."""

from reference.models.resnet import (
    RESNET_BLOCK_ORDER,
    ResNetBackbone,
    ResNetTopHead,
    frozen_prefix_len,
)


def build_backbone(cfg, dtype):
    if cfg.network.name == "vgg":
        raise NotImplementedError("the reference copy holds no VGG graph")
    fixed = cfg.network.FIXED_PARAMS
    n = frozen_prefix_len(fixed, RESNET_BLOCK_ORDER, requires=("bn",))
    fold = cfg.network.FOLD_BN
    return (
        ResNetBackbone(depth=cfg.network.depth, dtype=dtype, frozen_prefix=n,
                       fold_bn=fold),
        ResNetTopHead(depth=cfg.network.depth, dtype=dtype, fold_bn=fold),
    )
