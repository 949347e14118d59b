"""Host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` (a TraceMe): with no
profiler session open it costs a flag check, it needs no switch, and with
one open (``train_net --profile``, ``benchmark/run.py --trace 1``) it
lands in the same ``.xplane.pb`` and on the same clock as the device's
``XLA Ops`` line.  Spans nest per thread; across threads causality is
carried by the ids passed as keyword arguments (``step=``, ``batch=``,
``req=``), which the profiler stores as the event's stats.  Pass ids as
they are: the annotation formats them only while a session is open.

The names below are what ``benchmark/metrics/program_trace.py`` and
``benchmark/tools/idle_by_span.py`` read; PERF.md §3 lists each beside
the counter it wraps and the metric it is for.
"""

from __future__ import annotations

import threading

from jax.profiler import TraceAnnotation

# training, host side
LOADER_ASSEMBLE = "rcnn.loader.assemble"  # one batch built on one thread
LOADER_WAIT = "rcnn.loader.wait"          # consumer blocked on the pool
FEED_PLACE = "rcnn.feed.place"            # DeviceFeed worker: place_fn
FEED_WAIT = "rcnn.feed.wait"              # train loop starved by the feed
STEP_DISPATCH = "rcnn.step.dispatch"      # PipelinedLoop: one step_fn call
GUARD_SNAPSHOT = "rcnn.guard.snapshot"    # host_copy(state) of a window
GUARD_FETCH = "rcnn.guard.fetch"          # batched aux device_get
GUARD_FLUSH = "rcnn.guard.flush"          # all of a flush: fetch .. snapshot

# serving, host side
SERVE_PREPARE = "rcnn.serve.prepare"          # submit, caller's thread
SERVE_BATCH_WAIT = "rcnn.serve.batch_wait"    # assembler: empty queue + linger
SERVE_PICKUP = "rcnn.serve.pickup"            # one per batch, carries the ids
SERVE_ASSEMBLE = "rcnn.serve.assemble"        # pad + stack on the host
SERVE_SLOT_WAIT = "rcnn.serve.slot_wait"      # CompletionPool depth reached
SERVE_DISPATCH = "rcnn.serve.dispatch"        # sync, stage, async jit call
SERVE_FETCH = "rcnn.serve.fetch"              # host_copy of the outputs
SERVE_POSTPROCESS = "rcnn.serve.postprocess"  # detections_for .. resolve

TRAIN_SPANS = (
    LOADER_ASSEMBLE, LOADER_WAIT, FEED_PLACE, FEED_WAIT, STEP_DISPATCH,
    GUARD_SNAPSHOT, GUARD_FETCH, GUARD_FLUSH,
)

# device side: jax.named_scope components (metadata only).  ``backbone``,
# ``rpn`` and ``rcnn`` are the scopes flax opens for those submodules;
# ``roi_align`` is the pooling alone, inside ``roi_head`` and closed
# before the head's trunk (flax's ``<Model>._roi_features`` lies between).
# A pyramid also has flax's ``neck``, and under ``roi_align`` one more
# component a level, ``roi_align/p2`` .. ``roi_align/p5`` (models/fpn.py).
# The pooling's scope is named after ``ROI_MODE``: under ROI max pooling
# (VGG-16) the single-map graph opens ``roi_pool`` where ``roi_align``
# stands here, and flax's ``top_head`` holds fc6 / fc7.  Under Deformable
# ConvNets it is ``deform_roi_pool`` (both passes and the ``offset`` fc
# between them), and each of conv5's three deformable layers opens
# ``deform_conv`` inside flax's ``backbone/stage4/unit<i>`` (its offsets,
# its sampling and its product).
TRAIN_SCOPES = (
    "backbone", "rpn", "anchor_targets", "proposal", "roi_sample",
    "roi_head", "roi_align", "losses", "update",
)
FPN_SCOPES = ("neck",) + tuple(f"roi_align/p{lv}" for lv in range(2, 6))
ROI_POOL_SCOPES = ("roi_pool", "top_head")
DCN_SCOPES = ("deform_conv", "deform_roi_pool", "top_head")
SERVE_SCOPES = (
    "postprocess/decode", "postprocess/class_nms", "postprocess/cap",
    "postprocess/mask_select", "postprocess/mask_paste",
)

span = TraceAnnotation
#: is a profiler session open?  Guards ids that cost something to build.
enabled = TraceAnnotation.is_enabled

_ids = threading.local()


def set_batch(number: int) -> None:
    """The serving batch this thread works on from here: the engine sets
    it, the runner's and the completion pool's spans carry it."""
    _ids.batch = number


def current_batch() -> int:
    """0 on a thread no engine set it on (a replica pool's workers)."""
    return getattr(_ids, "batch", 0)
