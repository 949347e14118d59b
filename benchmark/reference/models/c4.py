"""``"graph": "c4"``: Faster R-CNN on a ResNet C4 backbone."""

from reference.models.faster_rcnn import FasterRCNN


def build(cfg):
    if cfg.network.USE_FPN:
        raise ValueError("graph c4 on a configuration that asks for FPN")
    return FasterRCNN(cfg)
