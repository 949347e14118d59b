"""The deformable convolutions' share of their roofline, read by SCOPE:
the least time the step's deformable layers can take over the device time
of every operation under the scope they open.

Least time: the larger of the layers' products at the bf16 peak and their
bytes at the memory's rate.  Products: each layer's 3×3 product (9·C ×
Cout a position) and its offset convolution's (9·C × offsets), forward
and the two of the backward pass (into the map, into the kernel).  Bytes:
the input map, the offsets and the output each read or written once, both
ways, and the kernels read once and their gradients written once.  The
sampling itself counts nothing: where a bilinear sample is read from is
the implementation's business, and the share reads the same work
whatever implements the layers later.  Shapes from the configuration's
graph file (``graphs/<graph>.py::deform_convs``)."""

from __future__ import annotations

import importlib.util
import os

from harness import flops
from harness.device import peak


def _program_trace():
    """``metrics/program_trace.py`` beside this file (the harness loads
    readers by file: there is no package to import it from)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "program_trace.py")
    spec = importlib.util.spec_from_file_location("bench_program_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def least_s(config, h: int, w: int, images: int, itemsize: int,
            peak_flops: float, peak_bytes_per_s: float):
    """→ {flops, bytes, least_s, bound} of one step's deformable layers."""
    model = config["model"]
    layers = flops.load_graph(model["graph"]).deform_convs(model, h, w)
    ops = nbytes = 0.0
    for d in layers:
        positions = images * d.map_h * d.map_w
        ops += 3 * 2.0 * positions * 9 * d.channels * (
            d.filters + d.offset_channels)
        nbytes += 2 * itemsize * positions * (
            d.channels + d.offset_channels + d.filters)
        nbytes += 2 * itemsize * 9 * d.channels * (
            d.filters + d.offset_channels)
    return {"flops": ops, "bytes": nbytes,
            "least_s": max(ops / peak_flops, nbytes / peak_bytes_per_s),
            "bound": "flops" if ops / peak_flops >= nbytes / peak_bytes_per_s
            else "bytes"}


def deform_conv_roofline(ctx, scope, module, itemsize):
    """Least seconds for one step's deformable layers over the device time
    a step under ``scope`` (``program_trace.scope_device_ms``).  None in an
    untraced run and where the program opens no such scope."""
    scope_ms = _program_trace().scope_device_ms(ctx, scope, module)
    if scope_ms is None:
        return None
    traffic = ctx["cell"].traffic
    h, w = traffic["bucket"]
    kind = ctx["device"]["kind"]
    least = least_s(ctx["cell"].config, h, w, int(traffic["batch_images"]),
                    itemsize, peak(kind, "flops_bf16"),
                    peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least["least_s"] / (scope_ms / 1e3)
