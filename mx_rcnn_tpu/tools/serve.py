"""Serving load test / endpoint smoke CLI.

Spins up the full online stack (ladder → batcher → engine), drives it
with the deterministic synthetic load generator, and prints the metrics
snapshot: p50/p95/p99 latency, throughput, batch occupancy, and the
compile counters that prove the bucket ladder held (misses ==
len(ladder) after warmup, and not one more).

Examples:
  # CPU smoke at a tiny config (no checkpoint needed)
  python -m mx_rcnn_tpu.tools.serve --small --requests 32

  # real checkpoint at the flagship config
  python -m mx_rcnn_tpu.tools.serve --network resnet --params final.pkl \
      --requests 256 --concurrency 16 --out serve_report.json

  # multi-tenant: a second family through the same batcher, plus a
  # mid-load hot-swap of it (the ``swap <model> <ckpt>`` admin command)
  python -m mx_rcnn_tpu.tools.serve --small \
      --model tenant=vgg:random:1 --swap tenant=ckpts/epoch_0002

  # mask family as a tenant: device postprocess ships selected
  # ``det_masks`` grids, not the raw (R, S, S, K) stack (ISSUE 14)
  python -m mx_rcnn_tpu.tools.serve --small \
      --model masks=mask_resnet_fpn:random:1

  # tenant-fair front door (ISSUE 16): two rate-limited tenants at 3:1
  # weights through the WFQ batcher, an elastic pool that may grow to 3
  # replicas, and the socket frontend listening on port 7447
  python -m mx_rcnn_tpu.tools.serve --small --replicas 1 --force_pool \
      --tenant acme=3:50 --tenant beta=1:20 \
      --autoscale_max 3 --frontend_port 7447
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import threading
import time
from typing import NamedTuple, Optional

import jax
import numpy as np

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import build_model
from mx_rcnn_tpu.serve.engine import ServingEngine
from mx_rcnn_tpu.serve.loadgen import DEFAULT_SIZES, run_load
from mx_rcnn_tpu.serve.registry import DEFAULT_MODEL, ModelRegistry
from mx_rcnn_tpu.serve.runner import ServeRunner

logger = logging.getLogger(__name__)


def small_config(network: str):
    """Tiny CPU-runnable config (integration-gate sizing): 128×128
    buckets plus a 96×128 one so mixed-size load exercises a real
    ladder."""
    cfg = generate_config(network, "PascalVOC")
    net_over = {"FIXED_PARAMS": ()}
    if not cfg.network.USE_FPN:
        net_over["ANCHOR_SCALES"] = (2, 4, 8)
    if cfg.network.depth > 50 and cfg.network.name == "resnet":
        net_over["depth"] = 50
    return cfg.replace(
        SHAPE_BUCKETS=((96, 128), (128, 128)),
        network=dataclasses.replace(cfg.network, **net_over),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((96, 128),)
        ),
        TEST=dataclasses.replace(
            cfg.TEST,
            RPN_PRE_NMS_TOP_N=200,
            RPN_POST_NMS_TOP_N=32,
            SCORE_THRESH=0.05,
        ),
    )


def random_params(model, cfg, seed: int = 0):
    """Random-init params at the config's first bucket (the no-checkpoint
    path — latency numbers stay valid; detections are noise)."""
    h, w = cfg.SHAPE_BUCKETS[0]
    return model.init(
        {"params": jax.random.key(seed)},
        np.zeros((1, h, w, 3), np.float32),
        np.array([[h, w, 1.0]], np.float32),
        train=False,
    )["params"]


def load_model_source(src: str, default_network: str, small: bool,
                      dataset: str):
    """``--model NAME=SPEC`` source → (model, cfg, params, digest).

    SPEC is ``[network:]source`` with source either a committed
    checkpoint directory (manifest-verified before registering) or
    ``random[:seed]``; the network defaults to ``--network``.
    """
    from mx_rcnn_tpu.config import NETWORKS

    network, source = default_network, src
    head, _, rest = src.partition(":")
    if rest and head in NETWORKS:
        network, source = head, rest
    cfg = small_config(network) if small else generate_config(
        network, dataset
    )
    model = build_model(cfg)
    if source.startswith("random"):
        _, _, seed_s = source.partition(":")
        params = random_params(model, cfg, int(seed_s) if seed_s else 0)
        return model, cfg, params, None
    from mx_rcnn_tpu.core.checkpoint import restore_tree, verify_manifest

    man = verify_manifest(source)  # the register-time trust gate
    tree = restore_tree(source)
    params = tree["params"] if isinstance(tree, dict) and "params" in tree \
        else tree
    return model, cfg, params, man.get("checksum")


def run_fleet(p, args):
    """--fleet N: spawn N backend processes (this same command with
    --backend), put a :class:`FleetGateway` over them, and drive the
    load through the gateway — the multi-host serve path with the real
    model stack in every process.  Children inherit this process's
    environment, platform included: a chip belongs to one process, so
    on a one-chip machine N > 1 only works with ``JAX_PLATFORMS=cpu``
    exported (one process per chip on a multi-chip host is the 4-chip
    replica item in ROADMAP.md)."""
    import sys

    from mx_rcnn_tpu.serve.fleet import FleetGateway, launch_backends
    from mx_rcnn_tpu.serve.loadgen import run_load as _run_load

    # children re-run this exact command line minus the fleet/output
    # flags, plus --backend (they serve; only the parent drives load)
    child = [sys.executable, "-m", "mx_rcnn_tpu.tools.serve"]
    skip_next = False
    for a in sys.argv[1:]:
        if skip_next:
            skip_next = False
            continue
        if a in ("--fleet", "--out", "--port_file"):
            skip_next = True
            continue
        if a.startswith(("--fleet=", "--out=", "--port_file=")):
            continue
        child.append(a)
    child.append("--backend")
    logger.info("spawning %d backend process(es)...", args.fleet)
    backends = launch_backends(child, args.fleet)
    # real-model forwards run seconds on CPU: a stub-scale hedge clock
    # would double-dispatch every request, so hedge late here
    gw = FleetGateway(
        [b.addr for b in backends], hedge_timeout=30.0
    ).start()
    sizes = ((72, 96), (96, 128), (64, 80)) if args.small else DEFAULT_SIZES
    tenant_names = [
        spec.partition("=")[0] for spec in args.tenant
    ] or None
    load_models = None
    if args.model:
        load_models = [None] + [
            spec.partition("=")[0] for spec in args.model
        ]
    try:
        report = _run_load(
            gw,
            num_requests=args.requests,
            concurrency=args.concurrency,
            sizes=sizes,
            seed=args.seed,
            deadline_s=(
                args.deadline_ms / 1000.0
                if args.deadline_ms is not None else None
            ),
            models=load_models,
            tenants=tenant_names,
        )
        report["fleet"] = gw.fleet_snapshot()
    finally:
        gw.stop()
        for b in backends:
            b.stop()
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        logger.info("wrote %s", args.out)


def parse_args(argv=None):
    """→ (parser, args); the parser rides along for ``p.error``."""
    p = argparse.ArgumentParser(description="Serving load test")
    p.add_argument("--network", default="resnet50",
                   choices=["vgg", "resnet", "resnet50", "resnet152",
                            "resnet_fpn", "mask_resnet_fpn"])
    p.add_argument("--dataset", default="PascalVOC",
                   choices=["PascalVOC", "PascalVOC0712", "coco"])
    p.add_argument("--params", default=None, help="params pickle (random "
                   "init when omitted — latency numbers are still valid)")
    p.add_argument("--small", action="store_true",
                   help="tiny config + small images for a CPU smoke run")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a ReplicaPool of this many health-"
                   "gated replicas (1 still exercises the pool path)")
    p.add_argument("--force_pool", action="store_true",
                   help="route through ReplicaPool even at --replicas 1")
    p.add_argument("--inflight_depth", type=int, default=2,
                   help="dispatches a replica keeps in flight (pool path): "
                   "batch N+1 stages and computes while batch N's outputs "
                   "fetch.  1 = the serial path, byte-identical results "
                   "at any depth")
    p.add_argument("--max_batch", type=int, default=4)
    p.add_argument("--linger_ms", type=float, default=5.0)
    p.add_argument("--max_queue", type=int, default=64)
    p.add_argument("--in_flight", type=int, default=2)
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--deadline_ms", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lane_mix", type=int, default=0, metavar="N",
                   help="tag one in N requests interactive (two-lane SLO "
                   "scheduling); 0 = untagged single-lane traffic")
    p.add_argument("--interactive_linger_ms", type=float, default=0.0,
                   help="linger for the interactive lane (default 0: "
                   "dispatch the moment a device slot frees)")
    p.add_argument("--bulk_age_limit", type=float, default=2.0,
                   help="seconds a bulk batch may wait before it takes "
                   "the next slot unconditionally (anti-starvation)")
    p.add_argument("--precision", default="float32",
                   choices=["float32", "bfloat16", "int8"],
                   help="serve-graph compute dtype; bfloat16 also folds "
                   "BN and is parity-gated against f32 at warmup (mask "
                   "families: the gate compares S×S mask grids too, and "
                   "the runner refuses bf16 mask models with the gate "
                   "disabled).  int8 serves per-channel weight-quantized "
                   "params (dequantize-on-use), gated by the same warmup "
                   "parity check")
    p.add_argument("--response_cache", type=int, default=0, metavar="N",
                   help="idempotent response cache capacity (entries); "
                   "0 disables.  Keyed by image digest per (model, "
                   "version), invalidated on hot-swap")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=[network:]SRC",
                   help="register an extra model family (repeatable); SRC "
                   "is a committed checkpoint dir or random[:seed].  Load "
                   "is then mixed across the default and every named "
                   "family through the one shared batcher")
    p.add_argument("--cascade", default=None,
                   metavar="CHEAP>FLAGSHIP[:THRESH]",
                   help="confidence-gated cascade: requests addressed to "
                   "FLAGSHIP first serve on the (registered) CHEAP "
                   "family; a pure-host gate escalates low-confidence "
                   "first passes back through the batcher to FLAGSHIP. "
                   "THRESH is the min top-score to ship the cheap answer "
                   "(default 0.5)")
    p.add_argument("--swap", default=None, metavar="MODEL=CKPT_DIR",
                   help="hot-swap MODEL to the checkpoint mid-load (the "
                   "'swap <model> <ckpt>' admin command, exercised live)")
    p.add_argument("--tenant", action="append", default=[],
                   metavar="NAME=WEIGHT[:RATE[:BURST]]",
                   help="register a tenant (repeatable): WFQ weight, "
                   "optional token-bucket rate (req/s) and burst.  Any "
                   "--tenant makes admission strict — untagged or unknown "
                   "tenants are rejected at submit.  Load is spread "
                   "uniformly over the registered tenants")
    p.add_argument("--autoscale_max", type=int, default=0, metavar="N",
                   help="attach the elastic autoscaler with this replica "
                   "ceiling (pool path only); 0 disables")
    p.add_argument("--autoscale_min", type=int, default=1,
                   help="autoscaler floor (default 1)")
    p.add_argument("--frontend_port", type=int, default=None, metavar="P",
                   help="also serve the length-prefixed wire protocol on "
                   "127.0.0.1:P for the duration of the load (0 = pick an "
                   "ephemeral port)")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="multi-host mode (ISSUE 19): spawn N backend "
                   "PROCESSES (each re-running this command with "
                   "--backend, full model stack per process), put a "
                   "FleetGateway over them, and drive the load through "
                   "the gateway")
    p.add_argument("--backend", action="store_true",
                   help="run as one fleet backend: build the configured "
                   "engine, serve the wire protocol, announce the port, "
                   "and block until stdin closes (no load generation)")
    p.add_argument("--port_file", default=None,
                   help="(backend mode) write the bound frontend port "
                   "here — how a spawning gateway finds this process")
    p.add_argument("--out", default=None, help="write the report JSON here")
    return p, p.parse_args(argv)


class ServeStack(NamedTuple):
    """What :func:`build_stack` assembles from the parsed arguments."""

    registry: ModelRegistry
    runner: object  # ServeRunner, or a ReplicaPool of them
    engine: ServingEngine
    sizes: tuple
    load_models: Optional[list]
    tenant_names: Optional[list]
    cascade_router: object


def build_stack(p, args) -> ServeStack:
    """Model(s) → registry → runner or pool → engine, exactly as the
    arguments say; nothing is started or warmed yet (``with
    stack.engine:`` does both).  Shared by :func:`main` and
    ``chip_smoke.py`` so the smoke serves through the CLI's own path."""
    if args.small:
        cfg = small_config(args.network)
        sizes = ((72, 96), (96, 128), (64, 80))
    else:
        cfg = generate_config(args.network, args.dataset)
        sizes = DEFAULT_SIZES
    model = build_model(cfg)
    if args.params:
        from mx_rcnn_tpu.utils.combine_model import load_params

        params = load_params(args.params)
    else:
        params = random_params(model, cfg, 0)
        logger.warning("no --params — serving a random-init model")

    # every family — the default plus each --model — lives in ONE
    # registry; the engine resolves (model, version) per batch, so adding
    # a tenant changes request schemas, not the serving stack
    registry = ModelRegistry()
    registry.register(DEFAULT_MODEL, model, cfg, params)
    load_models = None
    if args.model:
        load_models = [None]
        for spec in args.model:
            name, _, src = spec.partition("=")
            if not src:
                p.error(f"--model needs NAME=SRC, got {spec!r}")
            t_model, t_cfg, t_params, digest = load_model_source(
                src, args.network, args.small, args.dataset
            )
            registry.register(name, t_model, t_cfg, t_params, digest=digest,
                              source=src)
            load_models.append(name)
            logger.info("registered model %r from %s", name, src)

    precision = None if args.precision == "float32" else args.precision
    if args.replicas > 1 or args.force_pool:
        from mx_rcnn_tpu.serve.router import ReplicaPool, make_replica_factory

        factory = make_replica_factory(
            lambda registry, device: ServeRunner(
                registry=registry, device=device, max_batch=args.max_batch,
                precision=precision,
            ),
            registry=registry,
        )
        runner = ReplicaPool(factory, n_replicas=args.replicas,
                             inflight_depth=args.inflight_depth)
    else:
        runner = ServeRunner(
            registry=registry, max_batch=args.max_batch, precision=precision
        )
    response_cache = None
    if args.response_cache > 0:
        from mx_rcnn_tpu.serve.respcache import ResponseCache

        response_cache = ResponseCache(capacity=args.response_cache)
    # --tenant NAME=WEIGHT[:RATE[:BURST]] → a strict TenantTable; the
    # engine then runs token-bucket admission + WFQ release per tenant
    tenants = None
    tenant_names = None
    if args.tenant:
        from mx_rcnn_tpu.serve.tenancy import TenantTable

        tenants = TenantTable(strict=True)
        tenant_names = []
        for spec in args.tenant:
            name, _, rest = spec.partition("=")
            if not name or not rest:
                p.error(f"--tenant needs NAME=WEIGHT[:RATE[:BURST]], "
                        f"got {spec!r}")
            parts = rest.split(":")
            weight = float(parts[0])
            rate = float(parts[1]) if len(parts) > 1 and parts[1] else None
            burst = float(parts[2]) if len(parts) > 2 and parts[2] else None
            tenants.register(name, weight=weight, rate=rate, burst=burst)
            tenant_names.append(name)
    engine = ServingEngine(
        runner,
        max_linger=args.linger_ms / 1000.0,
        max_queue=args.max_queue,
        in_flight=args.in_flight,
        interactive_linger=args.interactive_linger_ms / 1000.0,
        bulk_age_limit=args.bulk_age_limit,
        response_cache=response_cache,
        tenants=tenants,
    )
    cascade_router = None
    if args.cascade:
        from mx_rcnn_tpu.serve.cascade import parse_cascade_spec

        try:
            policy = parse_cascade_spec(args.cascade)
        except ValueError as e:
            p.error(str(e))
        cascade_router = engine.attach_cascade(policy)
        logger.info("cascade: %s -> %s (min_score %.2f)",
                    policy.cheap, policy.flagship, policy.min_score)
    return ServeStack(registry, runner, engine, sizes, load_models,
                      tenant_names, cascade_router)


def main():
    from mx_rcnn_tpu.utils.platform import cli_bootstrap

    cli_bootstrap()
    p, args = parse_args()

    if args.fleet > 0:
        return run_fleet(p, args)

    (registry, runner, engine, sizes, load_models, tenant_names,
     cascade_router) = build_stack(p, args)
    logger.info(
        "warming up %d bucket(s) x %d model(s) x %d replica(s)...",
        len(runner.ladder), len(registry.model_ids()), args.replicas,
    )
    swap_result = {}

    def run_swap():
        # fire once the load is genuinely mid-flight, then block through
        # the admin surface so the report carries the full result
        smodel, _, sckpt = args.swap.partition("=")
        t_end = time.monotonic() + 120.0
        while (engine.metrics.completed < max(1, args.requests // 3)
               and time.monotonic() < t_end):
            time.sleep(0.005)
        t0 = time.monotonic()
        try:
            out = engine.admin(f"swap {smodel} {sckpt}")
            swap_result.update(out, wall_s=round(time.monotonic() - t0, 4))
        except Exception as e:  # noqa: BLE001 — report it, don't kill the load
            swap_result.update(error=repr(e))

    # --lane_mix N: a lane menu with one "interactive" per N-1 untagged
    # entries — run_load draws uniformly, so ~1/N of requests jump lanes
    load_lanes = None
    if args.lane_mix > 0:
        load_lanes = ["interactive"] + [None] * max(1, args.lane_mix - 1)

    with engine:
        if args.autoscale_max > 0:
            if not (args.replicas > 1 or args.force_pool):
                p.error("--autoscale_max needs the pool path "
                        "(--replicas > 1 or --force_pool)")
            from mx_rcnn_tpu.serve.autoscaler import ScalePolicy

            engine.attach_autoscaler(policy=ScalePolicy(
                min_replicas=args.autoscale_min,
                max_replicas=args.autoscale_max,
            ))
        frontend = None
        if args.backend or args.frontend_port is not None:
            from mx_rcnn_tpu.serve.frontend import Frontend

            frontend = Frontend(
                engine,
                port=(args.frontend_port
                      if args.frontend_port is not None else 0),
            )
            frontend.start()
            logger.info("frontend listening on 127.0.0.1:%d", frontend.port)
        if args.backend:
            # fleet backend: announce the port, serve until the spawning
            # gateway closes our stdin (SIGKILL needs no cooperation —
            # that's the chaos path)
            import os
            import sys

            print(f"FLEET_BACKEND port={frontend.port}", flush=True)
            if args.port_file:
                tmp = args.port_file + ".tmp"
                with open(tmp, "w") as f:
                    f.write(f"{frontend.port}\n")
                os.replace(tmp, args.port_file)
            try:
                sys.stdin.read()
            except KeyboardInterrupt:
                pass
            frontend.stop()
            engine.stop()  # idempotent — the with-exit becomes a no-op
            if hasattr(runner, "close"):
                runner.close()
            return
        swapper = None
        if args.swap:
            swapper = threading.Thread(target=run_swap, name="admin-swap")
            swapper.start()
        try:
            report = run_load(
                engine,
                num_requests=args.requests,
                concurrency=args.concurrency,
                sizes=sizes,
                seed=args.seed,
                deadline_s=(
                    args.deadline_ms / 1000.0
                    if args.deadline_ms is not None else None
                ),
                models=load_models,
                lanes=load_lanes,
                tenants=tenant_names,
            )
        finally:
            if frontend is not None:
                frontend.stop()
                report_frontend = frontend.snapshot()
        if frontend is not None:
            report["frontend"] = report_frontend
        if swapper is not None:
            swapper.join()
            report["swap"] = swap_result
        if cascade_router is not None:
            report["cascade"] = cascade_router.snapshot()
    if hasattr(runner, "close"):
        runner.close()
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        logger.info("wrote %s", args.out)


if __name__ == "__main__":
    main()
