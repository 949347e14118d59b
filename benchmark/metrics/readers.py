"""Readers of the metrics ``BENCHMARK.json`` names.  Each takes the run's
context (``cell``, ``run``: the driver's record, ``trace``: the reduced
device trace or None, ``device``) and returns a number, or None where it
finds nothing to read - never 0 for a share of a roofline or of a peak."""

from __future__ import annotations

from harness import flops, stats, trace as tr
from harness.device import peak


def setup_s(ctx):
    return ctx["run"]["setup_s"]


def window_rate(ctx, kind):
    """All the window's units (images trained, requests answered) over
    all the window's time."""
    run = ctx["run"]
    return run["rate"] if run["kind"] == kind else None


def latency_percentile_ms(ctx, q):
    lat = ctx["run"].get("latencies_ms")
    return stats.percentile(lat, q) if lat else None


def compile_s(ctx):
    return ctx["run"]["compile_s_setup"]


def device_idle_share(ctx, kind):
    if ctx["trace"] is None or ctx["run"]["kind"] != kind:
        return None
    return tr.idle_share_pct(ctx["trace"])


def module_device_ms(ctx, pattern):
    if ctx["trace"] is None:
        return None
    return tr.module_median_ms(ctx["trace"], pattern)


def _shape(ctx):
    traffic = ctx["cell"].traffic
    h, w = traffic["bucket"]
    return h, w, int(traffic["rois_per_image"])


def step_mfu(ctx):
    """Model FLOPs an image (forward + backward, from the shapes) times
    the window's images a second, over chips times the bf16 peak."""
    run = ctx["run"]
    if run["kind"] != "train":
        return None
    h, w, rois = _shape(ctx)
    per_image = flops.train_flops(ctx["cell"].config, h, w, rois)
    chips = ctx["device"]["count"]
    return 100.0 * per_image * run["rate"] / (
        chips * peak(ctx["device"]["kind"], "flops_bf16"))


def forward_mfu(ctx):
    """Forward FLOPs a served image (at the mean canvas of the requests
    answered) times images a second, over the bf16 peak."""
    run = ctx["run"]
    if run["kind"] != "serve" or not run.get("canvas_counts"):
        return None
    cfg = ctx["cell"].config
    rois = int(cfg["model"]["test"]["rpn_post_nms_top_n"])
    total = sum(
        n * flops.forward_flops(cfg, h, w, rois)
        for (h, w), n in run["canvas_counts"].items())
    window = run["t_end"] - run["t0"]
    chips = ctx["device"]["count"]
    return 100.0 * total / window / (
        chips * peak(ctx["device"]["kind"], "flops_bf16"))


def batch_occupancy(ctx):
    occ = ctx["run"].get("batch_occupancy")
    return None if occ is None else 100.0 * occ


def roi_align_roofline(ctx, pattern, itemsize, events_per_step):
    """Least time for the pools' bytes and operations over the summed
    device time of the ROIAlign forward and backward events.  The images
    pooled are counted from the events themselves (``events_per_step`` of
    them a step), so a trace cut inside a step does not skew the share."""
    if ctx["trace"] is None or ctx["run"]["kind"] != "train":
        return None
    secs, n = tr.ops_time_s(ctx["trace"], pattern)
    if not n:
        raise RuntimeError(
            f"no device operation matches {pattern!r}; heaviest names: "
            f"{[o[0] for o in tr.top_ops(ctx['trace'], 20)]}")
    h, w, rois = _shape(ctx)
    kind = ctx["device"]["kind"]
    least = flops.roi_align_least_s(
        ctx["cell"].config, h, w, rois, itemsize, True,
        peak(kind, "flops_bf16"), peak(kind, "hbm_bytes_per_s"))
    images = n / events_per_step * int(ctx["cell"].traffic["batch_images"])
    # least_s covers one image's forward and backward pools
    return 100.0 * least["least_s"] * images / secs
