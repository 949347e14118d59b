"""Round-4 probe: where do the backbone's 58 ms go, and what helps?

Times jitted fwd and fwd+bwd of backbone(+RPN-shaped loss) at flagship
shape (b8, 608x1024, bf16, frozen conv0+stage1), then variants:
- per-stage breakdown (fwd and fwd+bwd)
- BN folded into conv (structural conv+bias twin, timing only)
- space-to-depth conv0 (7x7s2 C3 -> 4x4s1 C12 equivalent shape)
- remat (jax.checkpoint) around stages

Usage: python scripts/probe_backbone.py [variant ...]
Variants: base stages folded s2d remat all
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

B, H, W = 8, 608, 1024
DTYPE = jnp.bfloat16


def timeit(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    # sync with a scalar fetch: the value cannot exist before the work ran
    _ = float(jnp.asarray(jax.tree_util.tree_leaves(r)[0]).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    _ = float(jnp.asarray(jax.tree_util.tree_leaves(r)[0]).ravel()[0])
    return (time.perf_counter() - t0) / iters * 1000


def report(tag, ms):
    print(f"{tag:<40s} {ms:8.2f} ms", flush=True)


def main():
    variants = sys.argv[1:] or ["base"]
    if "all" in variants:
        variants = ["base", "stages", "folded", "s2d", "remat"]

    from mx_rcnn_tpu.models.resnet import ResNetBackbone

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, H, W, 3).astype(np.float32))

    bb = ResNetBackbone(depth=101, dtype=DTYPE, frozen_prefix=2)
    params = bb.init(jax.random.key(0), x[:1])["params"]

    def fwd(p, xx):
        return bb.apply({"params": p}, xx).astype(jnp.float32).sum()

    def fwdbwd(p, xx):
        return jax.grad(fwd)(p, xx)

    if "base" in variants:
        report("backbone fwd", timeit(jax.jit(fwd), params, x))
        report("backbone fwd+bwd", timeit(jax.jit(fwdbwd), params, x))

    if "stages" in variants:
        # stage-by-stage: apply sub-modules through bound module access
        from mx_rcnn_tpu.models.resnet import ResNetStage
        import flax.linen as nn

        class Conv0(nn.Module):
            @nn.compact
            def __call__(self, x):
                from mx_rcnn_tpu.models.layers import FrozenBatchNorm, conv

                x = x.astype(DTYPE)
                x = conv(64, 7, 2, DTYPE, name="conv0")(x)
                x = FrozenBatchNorm(dtype=DTYPE, name="bn0")(x)
                x = nn.relu(x)
                return nn.max_pool(
                    x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1))
                )

        c0 = Conv0()
        p0 = {"conv0": params["conv0"], "bn0": params["bn0"]}
        f0 = jax.jit(lambda p, xx: c0.apply({"params": p}, xx))
        y0 = f0(p0, x)
        report("conv0+pool fwd", timeit(f0, p0, x))

        blocks = {"stage1": (64, 3, 1), "stage2": (128, 4, 2),
                  "stage3": (256, 23, 2)}
        y = y0
        for name, (filt, n, stride) in blocks.items():
            st = ResNetStage(filt, n, stride, DTYPE, name=name)
            sp = params[name]
            fs = jax.jit(lambda p, xx, st=st: st.apply({"params": p}, xx))
            gs = jax.jit(
                lambda p, xx, st=st: jax.grad(
                    lambda pp, aa: st.apply({"params": pp}, aa)
                    .astype(jnp.float32).sum()
                )(p, xx)
            )
            report(f"{name} fwd (in {y.shape[1]}x{y.shape[2]})",
                   timeit(fs, sp, y))
            report(f"{name} fwd+bwd", timeit(gs, sp, y))
            y = fs(sp, y)

    if "folded" in variants:
        # timing twin: BN affines folded into conv (conv + bias, no BN ops)
        import flax.linen as nn

        from mx_rcnn_tpu.models.layers import conv as mkconv

        class FoldedBottleneck(nn.Module):
            filters: int
            stride: int = 1

            @nn.compact
            def __call__(self, x):
                r = x
                y = mkconv(self.filters, 1, self.stride, DTYPE, name="conv1",
                           use_bias=True)(x)
                y = nn.relu(y)
                y = mkconv(self.filters, 3, 1, DTYPE, name="conv2",
                           use_bias=True)(y)
                y = nn.relu(y)
                y = mkconv(self.filters * 4, 1, 1, DTYPE, name="conv3",
                           use_bias=True)(y)
                if r.shape != y.shape:
                    r = mkconv(self.filters * 4, 1, self.stride, DTYPE,
                               name="sc", use_bias=True)(x)
                return nn.relu(y + r)

        class FoldedBackbone(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = x.astype(DTYPE)
                x = mkconv(64, 7, 2, DTYPE, name="conv0", use_bias=True)(x)
                x = nn.relu(x)
                x = nn.max_pool(x, (3, 3), strides=(2, 2),
                                padding=((1, 1), (1, 1)))
                x = jax.lax.stop_gradient(x)
                for name, (f, n, s) in {
                    "stage1": (64, 3, 1), "stage2": (128, 4, 2),
                    "stage3": (256, 23, 2),
                }.items():
                    for i in range(n):
                        x = FoldedBottleneck(
                            f, s if i == 0 else 1, name=f"{name}_u{i}"
                        )(x)
                    if name == "stage1":
                        x = jax.lax.stop_gradient(x)
                return x

        fb = FoldedBackbone()
        fparams = fb.init(jax.random.key(0), x[:1])["params"]

        def ffwd(p, xx):
            return fb.apply({"params": p}, xx).astype(jnp.float32).sum()

        report("folded fwd", timeit(jax.jit(ffwd), fparams, x))
        report("folded fwd+bwd",
               timeit(jax.jit(lambda p, xx: jax.grad(ffwd)(p, xx)), fparams, x))

    if "s2d" in variants:
        # conv0 as space-to-depth + 4x4 s1 conv (shape equivalent)
        def s2d_conv0(k, xx):
            v = xx.reshape(B, H // 2, 2, W // 2, 2, 3)
            v = v.transpose(0, 1, 3, 2, 4, 5).reshape(B, H // 2, W // 2, 12)
            return jax.lax.conv_general_dilated(
                v.astype(DTYPE), k, (1, 1), [(2, 1), (2, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        k = jnp.asarray(rng.rand(4, 4, 12, 64).astype(np.float32) * 0.01,
                        DTYPE)
        report("s2d conv0 fwd", timeit(jax.jit(s2d_conv0), k, x))

        def plain_conv0(k, xx):
            return jax.lax.conv_general_dilated(
                xx.astype(DTYPE), k, (2, 2), [(3, 3), (3, 3)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        k7 = jnp.asarray(rng.rand(7, 7, 3, 64).astype(np.float32) * 0.01,
                         DTYPE)
        report("plain conv0 fwd", timeit(jax.jit(plain_conv0), k7, x))

    if "remat" in variants:
        bb_r = ResNetBackbone(depth=101, dtype=DTYPE, frozen_prefix=2)

        def rfwd(p, xx):
            f = jax.checkpoint(
                lambda pp, aa: bb_r.apply({"params": pp}, aa)
            )
            return f(p, xx).astype(jnp.float32).sum()

        report("remat(whole) fwd+bwd",
               timeit(jax.jit(lambda p, xx: jax.grad(rfwd)(p, xx)), params, x))


if __name__ == "__main__":
    main()
