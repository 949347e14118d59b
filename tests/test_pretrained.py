"""Pretrained importer: torchvision-layout state_dicts → Flax param trees.

VERDICT r1 Missing #1: golden test proving imported conv1 outputs match a
torch-computed activation, plus structural round-trips for ResNet-50/101
and VGG-16 (synthetic state_dicts — no network access in this image).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mx_rcnn_tpu.models.resnet import ResNetBackbone, ResNetTopHead
from mx_rcnn_tpu.models.vgg import VGGBackbone, VGGTopHead
from mx_rcnn_tpu.utils.pretrained import (
    apply_pretrained,
    import_resnet,
    import_vgg16,
    load_state_dict,
    torchvision_pixel_stats,
)

_RESNET_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3)}


def fake_resnet_sd(rng, depth):
    """Random state_dict with exact torchvision ResNet naming/shapes."""
    sd = {"conv1.weight": rng.randn(64, 3, 7, 7).astype(np.float32)}
    for stat in ("weight", "bias", "running_mean", "running_var"):
        sd[f"bn1.{stat}"] = np.abs(rng.randn(64)).astype(np.float32) + 0.1
    cin = 64
    widths = (64, 128, 256, 512)
    for layer, n_units in enumerate(_RESNET_BLOCKS[depth], start=1):
        w = widths[layer - 1]
        for u in range(n_units):
            p = f"layer{layer}.{u}"
            sd[f"{p}.conv1.weight"] = rng.randn(w, cin, 1, 1).astype(np.float32)
            sd[f"{p}.conv2.weight"] = rng.randn(w, w, 3, 3).astype(np.float32)
            sd[f"{p}.conv3.weight"] = rng.randn(4 * w, w, 1, 1).astype(np.float32)
            for i in (1, 2, 3):
                c = w if i < 3 else 4 * w
                for stat in ("weight", "bias", "running_mean", "running_var"):
                    sd[f"{p}.bn{i}.{stat}"] = (
                        np.abs(rng.randn(c)).astype(np.float32) + 0.1
                    )
            if u == 0:
                sd[f"{p}.downsample.0.weight"] = rng.randn(
                    4 * w, cin, 1, 1
                ).astype(np.float32)
                for stat in ("weight", "bias", "running_mean", "running_var"):
                    sd[f"{p}.downsample.1.{stat}"] = (
                        np.abs(rng.randn(4 * w)).astype(np.float32) + 0.1
                    )
                cin = 4 * w
    return sd


def fake_vgg_sd(rng):
    feats = (0, 2, 5, 7, 10, 12, 14, 17, 19, 21, 24, 26, 28)
    chans = (64, 64, 128, 128, 256, 256, 256, 512, 512, 512, 512, 512, 512)
    sd = {}
    cin = 3
    for idx, c in zip(feats, chans):
        sd[f"features.{idx}.weight"] = (
            rng.randn(c, cin, 3, 3).astype(np.float32) * 0.05
        )
        sd[f"features.{idx}.bias"] = rng.randn(c).astype(np.float32) * 0.05
        cin = c
    sd["classifier.0.weight"] = rng.randn(4096, 25088).astype(np.float32) * 0.01
    sd["classifier.0.bias"] = rng.randn(4096).astype(np.float32) * 0.01
    sd["classifier.3.weight"] = rng.randn(4096, 4096).astype(np.float32) * 0.01
    sd["classifier.3.bias"] = rng.randn(4096).astype(np.float32) * 0.01
    return sd


def tree_shapes(t):
    return jax.tree_util.tree_map(lambda x: tuple(np.shape(x)), t)


class TestResNetImport:
    @pytest.mark.parametrize("depth", [50, 101])
    def test_structure_matches_model(self, rng, depth):
        sd = fake_resnet_sd(rng, depth)
        backbone, top_head = import_resnet(sd, depth)
        x = jnp.zeros((1, 64, 64, 3))
        bb_params = ResNetBackbone(depth=depth).init(jax.random.key(0), x)["params"]
        assert tree_shapes(backbone) == tree_shapes(bb_params)
        pooled = jnp.zeros((2, 14, 14, 1024))
        th_params = ResNetTopHead(depth=depth).init(jax.random.key(0), pooled)[
            "params"
        ]
        assert tree_shapes(top_head) == tree_shapes(th_params)

    def test_conv1_golden_vs_torch(self, rng):
        """Imported conv0+bn0+relu+maxpool must reproduce torch exactly."""
        import torch
        import torch.nn.functional as F

        sd = fake_resnet_sd(rng, 50)
        backbone, _ = import_resnet(sd, 50)
        x = rng.randn(1, 32, 32, 3).astype(np.float32)

        with torch.no_grad():
            xt = torch.from_numpy(x.transpose(0, 3, 1, 2))
            y = F.conv2d(xt, torch.from_numpy(sd["conv1.weight"]),
                         stride=2, padding=3)
            y = F.batch_norm(
                y,
                torch.from_numpy(sd["bn1.running_mean"]),
                torch.from_numpy(sd["bn1.running_var"]),
                torch.from_numpy(sd["bn1.weight"]),
                torch.from_numpy(sd["bn1.bias"]),
                training=False,
                eps=2e-5,
            )
            y = F.relu(y)
            y = F.max_pool2d(y, 3, stride=2, padding=1)
            expected = y.numpy().transpose(0, 2, 3, 1)

        # flax: run conv0/bn0/relu/pool via the backbone with stages cut
        bb = ResNetBackbone(depth=50)
        params = bb.init(jax.random.key(0), jnp.asarray(x))["params"]
        merged = jax.tree_util.tree_map(np.asarray, params)
        for k, v in backbone.items():
            merged[k] = v

        # reconstruct the stem output by calling the stage-1 input hook:
        # easiest exact probe is a backbone whose stages are identity —
        # use the full apply and capture the stem via a sliced module
        import flax.linen as fnn

        from mx_rcnn_tpu.models.layers import FrozenBatchNorm, conv

        class Stem(fnn.Module):
            @fnn.compact
            def __call__(self, x):
                x = conv(64, 7, 2, name="conv0")(x)
                x = FrozenBatchNorm(name="bn0")(x)
                x = fnn.relu(x)
                return fnn.max_pool(x, (3, 3), strides=(2, 2),
                                    padding=((1, 1), (1, 1)))

        stem_params = {"conv0": merged["conv0"], "bn0": merged["bn0"]}
        got = Stem().apply({"params": stem_params}, jnp.asarray(x))
        np.testing.assert_allclose(
            np.asarray(got), expected, rtol=2e-4, atol=2e-4
        )

    def test_fpn_layout_puts_stage4_in_backbone(self, rng):
        import dataclasses

        from mx_rcnn_tpu.config import generate_config
        from mx_rcnn_tpu.models import build_model

        sd = fake_resnet_sd(rng, 50)
        backbone, top_head = import_resnet(sd, 50, fpn=True)
        assert top_head == {}
        assert "stage4" in backbone
        cfg = generate_config("resnet_fpn", "PascalVOC")
        cfg = cfg.replace(
            network=dataclasses.replace(cfg.network, depth=50),
            dataset=dataclasses.replace(cfg.dataset, MAX_GT_BOXES=4),
        )
        model = build_model(cfg)
        params = model.init(
            {"params": jax.random.key(0), "sampling": jax.random.key(1)},
            jnp.zeros((1, 64, 64, 3)),
            jnp.asarray([[64.0, 64.0, 1.0]]),
            jnp.zeros((1, 4, 5)),
            jnp.zeros((1, 4), bool),
            train=True,
        )["params"]
        assert tree_shapes(backbone) == tree_shapes(
            jax.device_get(params["backbone"])
        )

    def test_apply_pretrained_merges_and_preserves_heads(self, rng):
        from mx_rcnn_tpu.config import generate_config
        from mx_rcnn_tpu.models import FasterRCNN

        cfg = generate_config("resnet50", "PascalVOC")
        model = FasterRCNN(cfg)
        h, w = 64, 64
        params = model.init(
            {"params": jax.random.key(0), "sampling": jax.random.key(1)},
            jnp.zeros((1, h, w, 3)),
            jnp.asarray([[h, w, 1.0]]),
            jnp.zeros((1, 8, 5)),
            jnp.zeros((1, 8), bool),
            train=True,
        )["params"]
        sd = fake_resnet_sd(rng, 50)
        out = apply_pretrained(jax.device_get(params), sd, "resnet", 50)
        np.testing.assert_array_equal(
            out["backbone"]["conv0"]["kernel"],
            sd["conv1.weight"].transpose(2, 3, 1, 0),
        )
        # detection heads untouched
        np.testing.assert_array_equal(
            out["rcnn"]["cls_score"]["kernel"],
            np.asarray(params["rcnn"]["cls_score"]["kernel"]),
        )

    def test_shape_mismatch_raises(self, rng):
        sd = fake_resnet_sd(rng, 50)
        sd["conv1.weight"] = np.zeros((64, 3, 3, 3), np.float32)
        with pytest.raises((ValueError, KeyError)):
            backbone, _ = import_resnet(sd, 50)
            x = jnp.zeros((1, 32, 32, 3))
            params = ResNetBackbone(depth=50).init(jax.random.key(0), x)["params"]
            from mx_rcnn_tpu.utils.pretrained import _merge

            _merge(jax.tree_util.tree_map(np.asarray, params), backbone, "bb")


class TestVGGImport:
    def test_structure_and_fc6_permutation(self, rng):
        import torch
        import torch.nn.functional as F

        sd = fake_vgg_sd(rng)
        backbone, top_head = import_vgg16(sd)
        x = jnp.zeros((1, 64, 64, 3))
        # shapes only: drawing the 103 M values of fc6 op by op takes minutes
        bb_params = jax.eval_shape(
            lambda: VGGBackbone().init(jax.random.key(0), x)["params"])
        assert tree_shapes(backbone) == tree_shapes(bb_params)
        pooled = jnp.zeros((2, 7, 7, 512))
        th_params = jax.eval_shape(
            lambda: VGGTopHead().init(jax.random.key(0), pooled)["params"])
        assert tree_shapes(top_head) == tree_shapes(th_params)

        # fc6 permutation golden: same pooled roi through torch Linear on
        # CHW flatten vs flax Dense on HWC flatten
        feat = rng.randn(2, 7, 7, 512).astype(np.float32)
        with torch.no_grad():
            flat_chw = torch.from_numpy(
                feat.transpose(0, 3, 1, 2).reshape(2, -1)
            )
            expected = F.linear(
                flat_chw,
                torch.from_numpy(sd["classifier.0.weight"]),
                torch.from_numpy(sd["classifier.0.bias"]),
            ).numpy()
        got = feat.reshape(2, -1) @ top_head["fc6"]["kernel"] + top_head["fc6"]["bias"]
        np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-3)


class TestLoadStateDict:
    def test_npz_and_pickle_roundtrip(self, rng, tmp_path):
        sd = {"a.weight": rng.randn(4, 3).astype(np.float32)}
        npz = tmp_path / "w.npz"
        np.savez(npz, **sd)
        got = load_state_dict(str(npz))
        np.testing.assert_array_equal(got["a.weight"], sd["a.weight"])

        import pickle

        pkl = tmp_path / "w.pkl"
        with open(pkl, "wb") as f:
            pickle.dump(sd, f)
        got = load_state_dict(str(pkl))
        np.testing.assert_array_equal(got["a.weight"], sd["a.weight"])

    def test_torch_pth(self, rng, tmp_path):
        import torch

        sd = {"a.weight": torch.from_numpy(rng.randn(4, 3).astype(np.float32))}
        p = tmp_path / "w.pth"
        torch.save(sd, p)
        got = load_state_dict(str(p))
        np.testing.assert_array_equal(got["a.weight"], sd["a.weight"].numpy())

    def test_pixel_stats(self):
        means, stds = torchvision_pixel_stats()
        assert means == pytest.approx((123.675, 116.28, 103.53))
        assert stds == pytest.approx((58.395, 57.12, 57.375))
