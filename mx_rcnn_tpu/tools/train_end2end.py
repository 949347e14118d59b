"""End-to-end Faster R-CNN training CLI.

Reference: ``train_end2end.py`` (argparse → generate_config → roidb →
AnchorLoader → MutableModule.fit with SGD/MultiFactorScheduler,
kvstore='device').  Same flow, TPU-native pieces: TrainLoader →
shard_map DP train step → Orbax checkpoints.

Example:
  python -m mx_rcnn_tpu.tools.train_end2end --network resnet \
      --dataset PascalVOC --synthetic 64 --epochs 2 --prefix model/e2e
"""

from __future__ import annotations

import argparse
import logging
import statistics
import time

import jax
import numpy as np

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.core.checkpoint import (
    PreemptionGuard,
    latest_checkpoint,
    load_checkpoint,
    load_restorable,
    prune_step_checkpoints,
    save_checkpoint,
)
from mx_rcnn_tpu.core.metrics import MetricTracker, Speedometer
from mx_rcnn_tpu.core.pipeline import DeviceFeed, PipelinedLoop, make_place_fn
from mx_rcnn_tpu.core.resilience import (
    DEGRADED_EXIT_CODE,
    DivergencePolicy,
    StepWatchdog,
)
from mx_rcnn_tpu.core.train import (
    create_train_state,
    make_lr_schedule,
    make_optimizer,
    make_train_step,
)
from mx_rcnn_tpu.data.loader import TrainLoader
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.parallel import (
    ElasticLoop,
    distributed,
    make_elastic_factory,
    make_mesh,
    make_parallel_train_step,
    replicate,
)
from mx_rcnn_tpu.utils.load_data import load_gt_roidb

logger = logging.getLogger(__name__)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="Train Faster R-CNN end-to-end")
    p.add_argument("--network", default="resnet",
                   choices=["vgg", "resnet", "resnet_dcn", "resnet50", "resnet152",
                            "resnet_fpn", "mask_resnet_fpn"])
    p.add_argument("--dataset", default="PascalVOC",
                   choices=["PascalVOC", "PascalVOC0712", "coco"])
    p.add_argument("--image_set", default=None)
    p.add_argument("--prefix", default="model/e2e", help="checkpoint dir")
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--batch_images", type=int, default=None, help="per-chip batch")
    p.add_argument("--grad_accum", type=int, default=1,
                   help="microbatches per optimizer update (gradient "
                        "accumulation for big effective batches)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--pretrained", default=None, metavar="CKPT",
                   help="ImageNet backbone checkpoint (.pth/.npz/pickle, "
                        "torchvision layout) imported before training")
    p.add_argument("--compute_dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="override network COMPUTE_DTYPE (bf16 rides the MXU)")
    p.add_argument("--no_flip", action="store_true")
    p.add_argument("--no_shuffle", action="store_true")
    p.add_argument("--frequent", type=int, default=20, help="logging interval")
    p.add_argument("--synthetic", type=int, default=0,
                   help="train on N synthetic images (no dataset needed)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max_steps", type=int, default=0,
                   help="stop after N steps (smoke runs)")
    p.add_argument("--cpu", type=int, default=0, metavar="N",
                   help="force the host backend with N virtual devices")
    p.add_argument("--elastic", action="store_true",
                   help="survive device loss: on a device fault, take an "
                        "emergency checkpoint, deterministically shrink "
                        "the data mesh to the survivors, replay the "
                        "in-flight window, and keep training (regrow is "
                        "attempted at checkpoint boundaries); a run that "
                        "finishes shrunken exits 76")
    p.add_argument("--dist_coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host training: process 0's coordinator "
                        "address (jax.distributed); on TPU pods usually "
                        "auto-discovered, so --dist_nprocs alone suffices")
    p.add_argument("--dist_nprocs", type=int, default=None,
                   help="multi-host training: total number of processes")
    p.add_argument("--dist_procid", type=int, default=None,
                   help="multi-host training: this process's id")
    p.add_argument("--metrics_jsonl", default=None, metavar="PATH",
                   help="append one JSON line of metrics per logging "
                        "interval (structured twin of the Speedometer log)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of steps 10-20 into "
                        "DIR (view with tensorboard/xprof): device ops under "
                        "their stage scopes and the program's own rcnn.* "
                        "host spans (utils/tracing.py) on one clock; "
                        "benchmark/tools/idle_by_span.py DIR tabulates it")
    # resilience (core/resilience.py): divergence recovery + hang watchdog
    p.add_argument("--step_timeout", type=float, default=0.0, metavar="SECS",
                   help="wall-clock watchdog per train step: a step that "
                        "exceeds this dumps a resumable checkpoint and "
                        "exits with code 75 instead of hanging (0 = off)")
    p.add_argument("--snapshot_every", type=int, default=10, metavar="N",
                   help="refresh the guarded loop's host-side rollback "
                        "snapshot every N accepted steps (1 = exact "
                        "rollback; higher amortizes the device->host "
                        "fetch)")
    p.add_argument("--spike_factor", type=float, default=20.0,
                   help="treat a step as diverged when its loss exceeds "
                        "this multiple of the running EMA")
    p.add_argument("--max_bad_batches", type=int, default=8,
                   help="abort (TrainingDiverged) after this many batches "
                        "are skipped via rollback")
    p.add_argument("--loader_failure_budget", type=int, default=None,
                   help="abort after this many records fail to load "
                        "(default: max(32, 1%% of the roidb))")
    # device-resident pipeline (core/pipeline.py): double-buffered
    # host->device feed + K-late aux fetch
    p.add_argument("--feed_depth", type=int, default=2, metavar="N",
                   help="device-feed double-buffer depth: batches staged "
                        "on device ahead of the running step")
    p.add_argument("--aux_interval", type=int, default=0, metavar="K",
                   help="fetch train aux every K steps instead of every "
                        "step (divergence checks run K late against the "
                        "retained window snapshot); 0 = auto: 1 on CPU "
                        "(exact sync-loop behavior), 8 on accelerators")
    return p.parse_args(argv)


def config_from_args(args):
    """The run's Config: ``generate_config`` plus the CLI's overrides."""
    import dataclasses

    cfg = generate_config(args.network, args.dataset)
    overrides = {}
    if args.lr is not None:
        overrides["LEARNING_RATE"] = args.lr
    if args.batch_images is not None:
        overrides["BATCH_IMAGES"] = args.batch_images
    if overrides:
        cfg = cfg.replace(TRAIN=dataclasses.replace(cfg.TRAIN, **overrides))
    net_overrides = {}
    if args.compute_dtype:
        net_overrides["COMPUTE_DTYPE"] = args.compute_dtype
    if args.pretrained:
        # torchvision-family checkpoints expect their own pixel stats
        from mx_rcnn_tpu.utils.pretrained import torchvision_pixel_stats

        means, stds = torchvision_pixel_stats()
        net_overrides["PIXEL_MEANS"] = means
        net_overrides["PIXEL_STDS"] = stds
    if net_overrides:
        cfg = cfg.replace(
            network=dataclasses.replace(cfg.network, **net_overrides)
        )
    return cfg


def flush_cycles_line(cycles) -> str:
    """The "host side:" line's account of the guard's flush cycles
    (``PipelinedLoop.stats()["cycles"]``): the median of each part."""
    if not cycles:
        return ""
    med = {k: statistics.median(c[k] for c in cycles)
           for k in cycles[0] if k.endswith("_ms")}
    return (
        f"; flush cycle, median of {len(cycles)}: chip without work "
        f"{med['idle_ms']:.0f} ms (drain {med['drain_ms']:.0f}, aux get "
        f"{med['aux_get_ms']:.0f}, check {med['check_ms']:.0f}, snapshot get "
        f"{med['snapshot_get_ms']:.0f} + copy {med['snapshot_copy_ms']:.0f}, "
        f"resume {med['resume_ms']:.0f} ms)"
    )


def train_net(args, report=None):
    """Run the training job ``args`` describes; returns the final state.

    ``report`` (optional dict) is filled on the way out with what the
    run did, for callers that must judge it (``main``'s exit code,
    ``chip_smoke.py``): ``steps`` dispatched by the loop and
    ``steps_applied`` to the optimizer state, the guard's
    ``skipped_batches`` / ``retried_steps`` / ``rollbacks``, the last
    verified ``losses`` as ``(step, loss)`` pairs, the host side's own
    counters — ``feed`` (``DeviceFeed.stats()`` summed over the epochs'
    feeds), ``pipeline`` (``PipelinedLoop.stats()``) and ``loader`` (the
    assembly pool's stats, summed; empty on the serial loader) —
    ``roi_levels`` (a pyramid's sampled rois by pooling level,
    ``num_rois_p2`` .., and where its streaming ROIAlign kernels run their
    live and walked (roi block, image) steps, ``roi_steps_live_p2`` /
    ``roi_steps_p2`` ..; summed over the fetched steps; empty otherwise),
    ``deform`` (Deformable ConvNets: each deformable layer's sampling
    points inside the map, ``deform_inside_u1`` .., of ``deform_points``
    a layer, the chunks of map rows its bands read,
    ``deform_window_chunks_u1`` .., of ``deform_window_chunks_min`` at
    zero offsets, and the second pooling pass's bins that kept no sample,
    ``deform_pool_empty_bins`` of ``deform_pool_bins``; summed over the
    fetched steps; empty otherwise) and on an elastic run ``elastic`` /
    ``degraded``."""
    import collections

    from mx_rcnn_tpu.utils.platform import cli_bootstrap

    cli_bootstrap()
    # order matters: platform selection must not probe devices before the
    # coordinator handshake, and the handshake must precede the first
    # backend initialization
    if args.cpu:
        from mx_rcnn_tpu.utils.platform import force_cpu, set_cpu_platform

        set_cpu_platform(args.cpu)
        distributed.initialize(
            args.dist_coordinator, args.dist_nprocs, args.dist_procid
        )
        force_cpu(args.cpu)
    else:
        distributed.initialize(
            args.dist_coordinator, args.dist_nprocs, args.dist_procid
        )

    cfg = config_from_args(args)

    n_chips = len(jax.devices())
    per_chip = cfg.TRAIN.BATCH_IMAGES
    # effective images per optimizer update: chips × per-chip microbatch
    # × accumulated microbatches
    global_batch = per_chip * n_chips * args.grad_accum
    logger.info(
        "devices=%d (%d local) per_chip_batch=%d grad_accum=%d global_batch=%d",
        n_chips, jax.local_device_count(), per_chip, args.grad_accum,
        global_batch,
    )

    _, roidb = load_gt_roidb(
        cfg,
        args.image_set,
        flip=cfg.TRAIN.FLIP and not args.no_flip,
        synthetic_size=args.synthetic,
    )
    logger.info("roidb size: %d", len(roidb))
    loader = TrainLoader(
        roidb, cfg, global_batch,
        shuffle=cfg.TRAIN.SHUFFLE and not args.no_shuffle, seed=args.seed,
        row_slice=(
            distributed.process_slice(global_batch)
            if jax.process_count() > 1 else None
        ),
        failure_budget=args.loader_failure_budget,
    )
    steps_per_epoch = max(len(loader), 1)

    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    init_batch = {
        "images": np.zeros((1, h, w, 3), np.float32),
        "im_info": np.array([[h, w, 1.0]], np.float32),
        "gt_boxes": np.zeros((1, cfg.dataset.MAX_GT_BOXES, 5), np.float32),
        "gt_valid": np.zeros((1, cfg.dataset.MAX_GT_BOXES), bool),
    }
    params = model.init(
        {"params": jax.random.key(args.seed), "sampling": jax.random.key(1)},
        init_batch["images"], init_batch["im_info"],
        init_batch["gt_boxes"], init_batch["gt_valid"], train=True,
    )["params"]
    if args.pretrained:
        # reference: load_param(pretrained) before attaching detection
        # heads (train_end2end.py :: train_net, SURVEY App. B)
        from mx_rcnn_tpu.utils.pretrained import apply_pretrained, load_state_dict

        params = apply_pretrained(
            jax.device_get(params), load_state_dict(args.pretrained),
            cfg.network.name, cfg.network.depth, fpn=cfg.network.USE_FPN,
        )
        logger.info("imported pretrained backbone from %s", args.pretrained)

    tx = make_optimizer(cfg, make_lr_schedule(cfg, steps_per_epoch))
    state = create_train_state(params, tx)
    begin_epoch = 0
    begin_batch = 0
    if args.resume and jax.process_count() == 1:
        # single-host: restore the newest VERIFIABLE dump, falling back
        # past corrupt/uncommitted ones (a kill mid-save leaves only an
        # orphaned .tmp that the manifest check already skips)
        found = load_restorable(args.prefix, state)
        if found is not None:
            (epoch, begin_batch), state = found
            begin_epoch = epoch
            loader.epoch = begin_epoch
            loader.skip_batches = begin_batch
            logger.info("resumed from epoch %d batch %d", epoch, begin_batch)
    elif args.resume:
        # multi-host: checkpoints are written by process 0 only; on
        # per-host disks the others may see nothing (or stale dirs), so
        # the resume point is process 0's decision everywhere — divergent
        # epoch/batch counters would desync the collectives.
        # latest_checkpoint already verified the manifest, so process 0's
        # pick is loadable short of on-disk bit rot (which raises loudly
        # as CheckpointCorrupt rather than desyncing the fleet).
        from jax.experimental import multihost_utils

        last = latest_checkpoint(args.prefix)
        agreed = multihost_utils.broadcast_one_to_all(
            np.asarray(last if last is not None else (-1, -1), np.int32)
        )
        last = tuple(int(x) for x in agreed)
        if last == (-1, -1):
            last = None
        if last is not None:
            epoch, begin_batch = last
            if jax.process_index() == 0:
                state = load_checkpoint(args.prefix, epoch, state, begin_batch)
            # ship process 0's restored state to hosts whose local
            # disk has no checkpoint (all processes must enter
            # replicate() with identical values)
            state = multihost_utils.broadcast_one_to_all(
                jax.device_get(state)
            )
            begin_epoch = epoch
            # replay the same shuffle stream a fresh run would have used
            # at this epoch (the loader keys its RNG on seed + epoch);
            # a mid-epoch (preemption) checkpoint additionally skips the
            # batches already consumed
            loader.epoch = begin_epoch
            loader.skip_batches = begin_batch
            logger.info("resumed from epoch %d batch %d", epoch, begin_batch)

    use_mesh = n_chips > 1
    use_elastic = args.elastic and use_mesh
    if use_elastic:
        step_fn = None  # the elastic loop owns (and rebuilds) the step
    elif use_mesh:
        mesh = make_mesh(n_data=n_chips, n_model=1)
        state = replicate(state, mesh)
        step_fn = make_parallel_train_step(
            model, tx, mesh, accum_steps=args.grad_accum
        )
    else:
        step_fn = make_train_step(model, tx, accum_steps=args.grad_accum)

    from mx_rcnn_tpu.utils.run_meta import save_run_meta

    if jax.process_index() == 0:
        save_run_meta(args.prefix, cfg)

    # resilience + pipeline: every step runs under the pipelined guarded
    # loop (NaN/spike → retry with LR backoff → rollback + skip, K steps
    # late when --aux_interval > 1); an optional watchdog turns a hung
    # step into a resumable checkpoint + exit 75 instead of an rc=124
    # external kill (the MULTICHIP_r04 failure mode)
    aux_interval = args.aux_interval or (
        1 if jax.default_backend() == "cpu" else 8
    )
    guard_policy = DivergencePolicy(
        spike_factor=args.spike_factor,
        max_bad_batches=args.max_bad_batches,
    )
    loop_pos = {"epoch": begin_epoch, "batch": begin_batch}
    eloop = None
    if use_elastic:
        # stream-step → (epoch, batch) translation for emergency dumps:
        # refreshed at each epoch start
        epoch_pos = {"start_step": 0, "off": begin_batch}

        def _emergency_ckpt(host_state, stream_step, meta):
            if jax.process_index() != 0:
                return None
            bpos = max(
                0, stream_step - epoch_pos["start_step"] + epoch_pos["off"]
            )
            return save_checkpoint(
                args.prefix, host_state, loop_pos["epoch"], bpos, meta=meta
            )

        eloop = ElasticLoop(
            make_elastic_factory(model, tx, accum_steps=args.grad_accum),
            n_chips,
            policy=guard_policy,
            aux_interval=aux_interval,
            checkpoint_fn=_emergency_ckpt,
        )
        # state placement is the elastic context's job (and is redone on
        # every membership change)
        state = eloop.ctx.place_state(jax.device_get(state))
        pipeline = eloop.pipe  # shared watchdog/stats surface
        # the elastic loop needs HOST batches — it truncates to the
        # survivor count and shards to the CURRENT mesh itself
        batch_place = lambda b: b  # noqa: E731
        step_loop = eloop
    else:
        pipeline = PipelinedLoop(
            step_fn,
            policy=guard_policy,
            snapshot_every=args.snapshot_every,
            place_fn=(lambda t: replicate(t, mesh)) if use_mesh else None,
            aux_interval=aux_interval,
        )
        # one placement path for every topology: single chip, DP mesh
        # (shard_batch), multi-host (globalize_batch) — run by the feed's
        # worker thread so batch N+1's transfer overlaps step N
        batch_place = make_place_fn(mesh if use_mesh else None)
        step_loop = pipeline
    if args.step_timeout > 0:
        def _watchdog_dump():
            snap = pipeline.last_snapshot
            if snap is None or jax.process_index() != 0:
                return None
            # the snapshot lags the stream by steps_since_snapshot —
            # name the dump at ITS position so resume re-consumes the
            # un-snapshotted batches rather than silently skipping them
            batch_pos = max(
                0, loop_pos["batch"] - pipeline.steps_since_snapshot
            )
            return save_checkpoint(
                args.prefix, snap, loop_pos["epoch"], batch_pos
            )

        pipeline.watchdog = StepWatchdog(
            args.step_timeout, dump_fn=_watchdog_dump
        )

    STOP_VOTE_EVERY = 10

    def _stop_agreed(local_stop: bool, step: int) -> bool:
        """Preemption is delivered per-process; every process must agree
        on the stop step or the others hang in the next collective.
        Multi-host, the vote is a blocking cross-host allgather, so it
        runs every STOP_VOTE_EVERY steps (same step on every process —
        ``step`` is process-invariant) rather than every step; preemption
        grace periods are tens of seconds, so the added latency is noise."""
        if jax.process_count() == 1:
            return local_stop
        if step % STOP_VOTE_EVERY:
            return False
        from jax.experimental import multihost_utils

        votes = multihost_utils.process_allgather(
            np.asarray(local_stop, np.int32)
        )
        return bool(np.asarray(votes).any())

    tracker = MetricTracker()
    # bounded: a long run must not grow a per-step list on the host
    losses = collections.deque(maxlen=64)
    step0 = int(jax.device_get(state.step))
    # only process 0 writes the metrics file: every process computing
    # global-batch throughput into a shared path would duplicate records
    jsonl = args.metrics_jsonl if jax.process_index() == 0 else None
    speedo = Speedometer(global_batch, args.frequent, jsonl_path=jsonl)
    rng = jax.random.key(args.seed + 123)
    total_steps = 0
    tracing = False
    preempted = False
    preempt_guard = PreemptionGuard()

    feed_totals: collections.Counter = collections.Counter()
    loader_totals: collections.Counter = collections.Counter()

    def add_stats(totals, stats):
        # sums over the epochs' feeds; ``depth``/``workers`` are settings
        # and ``occupancy`` a ratio: the last epoch's stand
        for k, v in stats.items():
            if k in ("depth", "workers", "occupancy", "queue_depth_max"):
                totals[k] = v
            else:
                totals[k] += v

    # a pyramid's sampled rois by the level that pools them, and the
    # streaming kernels' live steps of those they walk (``num_rois_p2`` ..,
    # ``roi_steps_live_p2`` / ``roi_steps_p2`` .. in the step's aux),
    # summed over the fetched steps
    roi_level_totals: collections.Counter = collections.Counter()
    # Deformable ConvNets' in-map counters (``deform_*`` in the aux)
    deform_totals: collections.Counter = collections.Counter()

    def deliver(ready):
        for idx, aux in ready:
            values = {k: float(v) for k, v in aux.items()}
            tracker.update(values)
            losses.append((idx, values["loss"]))
            roi_level_totals.update(
                {k: v for k, v in values.items()
                 if k.startswith(("num_rois_p", "roi_steps_"))})
            deform_totals.update(
                {k: v for k, v in values.items() if k.startswith("deform_")})

    def flush_pipeline(state):
        # force the deferred aux checks before any checkpoint/summary:
        # a divergence inside the window must roll back NOW, not after
        # the bad state has been persisted
        state, ready, _ok = step_loop.flush(state)
        deliver(ready)
        return state

    try:
        for epoch in range(begin_epoch, args.epochs):
            batch_in_epoch = begin_batch if epoch == begin_epoch else 0
            if use_elastic:
                epoch_pos["start_step"] = eloop.pipe.next_index
                epoch_pos["off"] = batch_in_epoch
            batches = iter(loader)
            feed = DeviceFeed(
                batches, place_fn=batch_place, depth=args.feed_depth
            )
            try:
                for batch in feed:
                    loop_pos["epoch"], loop_pos["batch"] = epoch, batch_in_epoch
                    # profiler window: skip compile/warmup, capture steady
                    # state (SURVEY §5.2 — the reference had a Speedometer)
                    if args.profile and total_steps == 10:
                        jax.profiler.start_trace(args.profile)
                        tracing = True
                    state, ready, _step_ok = step_loop.step(state, batch, rng)
                    deliver(ready)
                    total_steps += 1
                    batch_in_epoch += 1
                    if args.profile and total_steps == 20:
                        jax.profiler.stop_trace()
                        tracing = False
                        logger.info("profiler trace written to %s", args.profile)
                    speedo(epoch, total_steps, tracker)
                    if _stop_agreed(preempt_guard.should_stop, total_steps):
                        # preemption: mid-epoch checkpoint resume picks up
                        preempted = True
                        state = flush_pipeline(state)
                        if jax.process_index() == 0:
                            path = save_checkpoint(
                                args.prefix, jax.device_get(state),
                                epoch, batch_in_epoch,
                            )
                            logger.info(
                                "preempted at epoch %d batch %d — checkpoint -> %s",
                                epoch, batch_in_epoch, path,
                            )
                        break
                    if args.max_steps and total_steps >= args.max_steps:
                        break
            finally:
                feed.close()
                add_stats(feed_totals, feed.stats())
                if hasattr(batches, "stats"):  # the assembly pool's stream
                    add_stats(loader_totals, batches.stats())
            state = flush_pipeline(state)
            if preempted:
                break
            if jax.process_index() == 0:
                path = save_checkpoint(
                    args.prefix, jax.device_get(state), epoch + 1
                )
                logger.info("Epoch[%d] checkpoint -> %s", epoch, path)
                # preemption dumps from this epoch are now superseded
                prune_step_checkpoints(args.prefix, epoch)
            if use_elastic:
                # regrow only here: the boundary save above is the state
                # a failed regrow would fall back to
                state, regrown = eloop.checkpoint_boundary(state)
                if regrown:
                    logger.info(
                        "elastic: regrown to %d replicas", len(eloop.active)
                    )
            if args.max_steps and total_steps >= args.max_steps:
                break
    finally:
        preempt_guard.uninstall()
        if pipeline.skipped_batches or loader.record_failures:
            logger.warning(
                "resilience summary: %d poison batch(es) skipped via "
                "rollback (%d step retries), %d record(s) failed to load "
                "(%d substituted, %d batches dropped)",
                pipeline.skipped_batches, pipeline.retried_steps,
                loader.record_failures, loader.substituted_records,
                loader.dropped_batches,
            )
        if tracing:
            # run ended inside the capture window — flush what we have
            jax.profiler.stop_trace()
            logger.info(
                "profiler trace (short run) written to %s", args.profile
            )
        pipe_stats = pipeline.stats()
        logger.info(
            "host side: feed starved %d of %d gets after the first (waited "
            "%.2fs), assembly pool starved %d of %d (waited %.2fs), %d state "
            "snapshot(s) %.0f ms, %d fetch stall(s) %.0f ms%s",
            feed_totals["feed_starved_after_first"], feed_totals["fed"],
            feed_totals["wait_s"], loader_totals["starved_after_first"],
            loader_totals["yielded"], loader_totals["wait_s"],
            pipe_stats["snapshots"], pipe_stats["snapshot_ms"],
            pipe_stats["fetch_stalls"], pipe_stats["fetch_stall_ms"],
            flush_cycles_line(pipe_stats["cycles"]),
        )
        if roi_level_totals:
            logger.info(
                "host side: sampled rois by pyramid level: %s",
                ", ".join(f"{k[len('num_rois_'):]} {v:.0f}"
                          for k, v in sorted(roi_level_totals.items())
                          if k.startswith("num_rois_")),
            )
            live = {k[len("roi_steps_live_"):]: v
                    for k, v in roi_level_totals.items()
                    if k.startswith("roi_steps_live_")}
            if live:
                logger.info(
                    "host side: streaming ROIAlign steps with a roi of their "
                    "level, of those walked: %s",
                    ", ".join(
                        f"{lv} {v:.0f} of "
                        f"{roi_level_totals[f'roi_steps_{lv}']:.0f} "
                        f"({v / roi_level_totals[f'roi_steps_{lv}']:.1%})"
                        for lv, v in sorted(live.items())),
                )
        if deform_totals:
            d = deform_totals
            logger.info(
                "host side: deformable sampling points inside the map: %s; "
                "window chunks over their zero-offset count: %s; "
                "pooled bins with no sample %.0f of %.0f (%.1f%%)",
                ", ".join(
                    f"{k[len('deform_inside_'):]} "
                    f"{v / d['deform_points']:.1%}"
                    for k, v in sorted(d.items())
                    if k.startswith("deform_inside_")),
                ", ".join(
                    f"{k[len('deform_window_chunks_'):]} "
                    f"{v / d['deform_window_chunks_min']:.3f}"
                    for k, v in sorted(d.items())
                    if k.startswith("deform_window_chunks_u")),
                d["deform_pool_empty_bins"], d["deform_pool_bins"],
                100.0 * d["deform_pool_empty_bins"] / d["deform_pool_bins"],
            )
        if report is not None:
            report.update(
                steps=total_steps,
                skipped_batches=pipeline.skipped_batches,
                retried_steps=pipeline.retried_steps,
                rollbacks=pipeline.rollbacks,
                losses=list(losses),
                feed=dict(feed_totals),
                pipeline=pipe_stats,
                loader=dict(loader_totals),
                roi_levels=dict(roi_level_totals),
                deform=dict(deform_totals),
            )
        if use_elastic:
            if eloop.monitor.shrinks:
                logger.warning(
                    "elastic summary: %d shrink(s), %d regrow(s), %d "
                    "emergency checkpoint(s), %d step(s) replayed, "
                    "%.2fs total recovery; final mesh %d/%d replicas",
                    eloop.monitor.shrinks, eloop.monitor.regrows,
                    len(eloop.emergency_ckpts), eloop.replayed_steps,
                    eloop.recovery_s, len(eloop.active), n_chips,
                )
            if report is not None:
                report["elastic"] = eloop.stats()
                report["degraded"] = eloop.degraded
    if report is not None:
        report["steps_applied"] = int(jax.device_get(state.step)) - step0
    return state


def main():
    import sys

    report = {}
    train_net(parse_args(), report=report)
    if report.get("degraded"):
        # the run FINISHED, but on a shrunken mesh — tell the scheduler
        # so it can reschedule at full size if it cares
        sys.exit(DEGRADED_EXIT_CODE)


if __name__ == "__main__":
    main()
