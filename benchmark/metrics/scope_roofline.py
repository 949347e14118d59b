"""A pooling stage's share of its roofline, read by SCOPE: the least time
for the pools' bytes and operations (``harness/flops.py::
roi_align_least_s`` over the pools the configuration's graph file states)
over the device time of every operation under one stage scope.  Where
``readers.roi_align_roofline`` finds its kernels by an operation's name,
this reads whatever the program runs under the scope, so the share reads
the same work when another implementation takes the scope over."""

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


def pool_scope_roofline(ctx, scope, module, itemsize):
    """Least seconds for one step's pools, forward and backward, over the
    device time a step under ``scope`` as ``program_trace.scope_device_ms``
    reads it (the union of the scope's operations' intervals over the
    programs matching ``module``).  None in an untraced run and where the
    program opens no such scope."""
    scope_ms = _program_trace().scope_device_ms(ctx, scope, module)
    if scope_ms is None:
        return None
    traffic = ctx["cell"].traffic
    h, w = traffic["bucket"]
    kind = ctx["device"]["kind"]
    least = flops.roi_align_least_s(
        ctx["cell"].config, h, w, int(traffic["rois_per_image"]), itemsize,
        True, peak(kind, "flops_bf16"), peak(kind, "hbm_bytes_per_s"))
    return (100.0 * least["least_s"] * int(traffic["batch_images"])
            / (scope_ms / 1e3))
