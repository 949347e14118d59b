"""The program's own spans and stage scopes (``utils/tracing.py``): that a
profiler session sees every span with the ids that join them, that the
counters beside them reach ``train_net``'s report, that every stage scope
is in the lowered programs with the ROIAlign kernels still found the way
the benchmark finds them, and that none of it costs anything or changes a
number while no session is open."""

import dataclasses
import glob
import json
import os
import re
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.utils import tracing

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helpers
def _spans(trace_dir):
    """{name: [(thread, start_ns, ids), ...]} of the ``rcnn.*`` events on
    the host planes of the session written under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = {}
    thread = 0
    for plane in ProfileData.from_file(found[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith("rcnn."):
                    out.setdefault(e.name, []).append(
                        (thread, e.start_ns, dict(e.stats)))
    return out


def _session(trace_dir):
    """A profiler session without the Python call tracer (the spans are
    TraceMes; the call tracer only makes the file large)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def _numbers(value):
    """A list id as it comes back: its ``str``, or the lone number."""
    return [int(x) for x in re.findall(r"\d+", str(value))]


def _tiny_generate_config(network, dataset):
    cfg = generate_config(network, dataset)
    return cfg.replace(
        SHAPE_BUCKETS=((96, 96),),
        TRAIN=dataclasses.replace(
            cfg.TRAIN, RPN_PRE_NMS_TOP_N=256, RPN_POST_NMS_TOP_N=32,
            BATCH_ROIS=16, RPN_BATCH_SIZE=32,
        ),
        dataset=dataclasses.replace(
            cfg.dataset, SCALES=((96, 96),), MAX_GT_BOXES=8
        ),
    )


# ------------------------------------------------------- (a) training spans
@pytest.fixture(scope="module")
def traced_train(tmp_path_factory):
    """Six steps of a tiny ``train_net`` (two assembly threads, the guard
    reading every second step) inside one profiler session."""
    from mx_rcnn_tpu.tools import train_end2end as cli

    tmp = tmp_path_factory.mktemp("train")
    mp = pytest.MonkeyPatch()
    mp.setattr(cli, "generate_config", _tiny_generate_config)
    mp.setenv("MX_RCNN_ASSEMBLY_WORKERS", "2")
    args = cli.parse_args([
        "--network", "resnet50", "--dataset", "PascalVOC",
        "--synthetic", "64", "--epochs", "1", "--frequent", "1",
        "--batch_images", "1", "--lr", "0.0005", "--max_steps", "6",
        "--aux_interval", "2", "--prefix", str(tmp / "ckpt"),
    ])
    report = {}
    _session(tmp / "trace")
    try:
        cli.train_net(args, report=report)
    finally:
        jax.profiler.stop_trace()
        mp.undo()
    return _spans(str(tmp / "trace")), report


@pytest.mark.parametrize("name", tracing.TRAIN_SPANS)
def test_train_span_is_recorded(traced_train, name):
    spans, _report = traced_train
    assert spans.get(name), sorted(spans)


def test_step_dispatch_carries_consecutive_steps(traced_train):
    spans, report = traced_train
    steps = [ids["step"] for _t, _s, ids in sorted(
        spans[tracing.STEP_DISPATCH], key=lambda s: s[1])]
    assert steps == list(range(report["steps"])) == list(range(6))
    # one loop thread; the assembly spans name the plan's batches
    assert len({t for t, _s, _i in spans[tracing.STEP_DISPATCH]}) == 1
    built = sorted(ids["batch"] for _t, _s, ids in
                   spans[tracing.LOADER_ASSEMBLE])
    assert built[:6] == list(range(6))


@pytest.mark.parametrize("key,fields", [
    ("feed", ("fed", "feed_starved_after_first", "wait_s")),
    ("pipeline", ("snapshots", "snapshot_ms", "fetch_stall_ms", "flushes",
                  "cycles")),
    ("loader", ("workers", "submitted", "wait_s")),
])
def test_report_carries_the_host_sides_counters(traced_train, key, fields):
    _spans_, report = traced_train
    assert set(fields) <= set(report[key]), report[key]
    json.dumps(report[key])  # the benchmark's driver copies it as it is


def test_counters_agree_with_the_spans_they_sit_beside(traced_train):
    spans, report = traced_train
    pipe = report["pipeline"]
    # interval 2 over 6 steps: the head-of-stream snapshot and one a flush
    assert pipe["snapshots"] == len(spans[tracing.GUARD_SNAPSHOT]) == 4
    assert pipe["fetches"] == len(spans[tracing.GUARD_FETCH]) == 3
    assert pipe["flushes"] == len(spans[tracing.GUARD_FLUSH]) == 3
    assert report["feed"]["feed_starved"] == len(spans[tracing.FEED_WAIT])
    assert report["feed"]["fed"] == 6 <= len(spans[tracing.FEED_PLACE])


def test_flush_spans_join_the_cycle_records(traced_train):
    """A flush span's ``step`` is its window's first index, the key of
    the host's record of the cycle; the closing flush (steps 4-5, no
    dispatch after it) has a span and no record."""
    spans, report = traced_train
    flushes = sorted((ids["step"], ids["n"]) for _t, _s, ids in
                     spans[tracing.GUARD_FLUSH])
    assert flushes == [(0, 2), (2, 2), (4, 2)]
    cycles = report["pipeline"]["cycles"]
    assert [(c["step"], c["n"]) for c in cycles] == flushes[:-1]
    assert all(c["idle_ms"] >= c["snapshot_get_ms"] + c["snapshot_copy_ms"]
               for c in cycles)
    assert {t for t, _s, _i in spans[tracing.GUARD_FLUSH]} == {
        t for t, _s, _i in spans[tracing.STEP_DISPATCH]}


# -------------------------------------------------------- (b) serving spans
BUCKET = (64, 64)


@pytest.fixture(scope="module")
def tiny_runner():
    from mx_rcnn_tpu.models import build_model
    from mx_rcnn_tpu.serve.runner import ServeRunner

    cfg = generate_config("resnet50", "PascalVOC")
    cfg = cfg.replace(
        SHAPE_BUCKETS=(BUCKET,),
        network=dataclasses.replace(
            cfg.network, ANCHOR_SCALES=(2, 4, 8), FIXED_PARAMS=()),
        dataset=dataclasses.replace(
            cfg.dataset, NUM_CLASSES=4, SCALES=((64, 64),)),
        TEST=dataclasses.replace(
            cfg.TEST, RPN_PRE_NMS_TOP_N=100, RPN_POST_NMS_TOP_N=16,
            SCORE_THRESH=0.05),
    )
    model = build_model(cfg)
    params = model.init(
        {"params": jax.random.key(0)},
        np.zeros((1,) + BUCKET + (3,), np.float32),
        np.array([list(BUCKET) + [1.0]], np.float32), train=False,
    )["params"]
    runner = ServeRunner(model, params, cfg, max_batch=2)
    runner.warmup()
    return runner


@pytest.fixture(scope="module")
def traced_serve(tiny_runner, tmp_path_factory):
    """Seven requests through a tiny engine inside one profiler session.
    → (spans, how many were answered)."""
    from mx_rcnn_tpu.serve.engine import ServingEngine

    tmp = tmp_path_factory.mktemp("serve")
    rng = np.random.RandomState(0)
    images = [rng.randint(0, 256, (48, 56, 3)).astype(np.float32)
              for _ in range(7)]
    _session(tmp / "trace")
    try:
        with ServingEngine(tiny_runner, max_linger=0.02, in_flight=2) as eng:
            futures = [eng.submit(im) for im in images]
            answered = sum(f.result(timeout=300) is not None for f in futures)
    finally:
        jax.profiler.stop_trace()
    return _spans(str(tmp / "trace")), answered


def test_every_request_is_prepared_and_picked_up_once(traced_serve):
    spans, answered = traced_serve
    assert answered == 7
    prepared = [ids["req"] for _t, _s, ids in spans[tracing.SERVE_PREPARE]]
    picked = [r for _t, _s, ids in spans[tracing.SERVE_PICKUP]
              for r in _numbers(ids["reqs"])]
    assert len(prepared) == len(set(prepared)) == 7
    assert sorted(picked) == sorted(prepared)
    for _t, _s, ids in spans[tracing.SERVE_PICKUP]:
        waits = re.findall(r"\d+(?:\.\d+)?", str(ids["wait_ms_each"]))
        assert len(waits) == ids["n"] == len(_numbers(ids["reqs"]))
        assert float(ids["wait_ms"]) == max(float(w) for w in waits)


@pytest.mark.parametrize("name", [
    tracing.SERVE_ASSEMBLE, tracing.SERVE_DISPATCH, tracing.SERVE_FETCH,
    tracing.SERVE_POSTPROCESS,
])
def test_every_batch_passes_each_stage_once(traced_serve, name):
    spans, _answered = traced_serve
    batches = sorted(ids["batch"] for _t, _s, ids in
                     spans[tracing.SERVE_PICKUP])
    assert batches == list(range(batches[0], batches[0] + len(batches)))
    # batch 0 is work outside an engine batch: start()'s warm-up probes
    assert sorted(ids["batch"] for _t, _s, ids in spans[name]
                  if ids["batch"]) == batches


def test_the_assembler_waits_under_its_own_spans(traced_serve):
    spans, _answered = traced_serve
    assert spans.get(tracing.SERVE_BATCH_WAIT)
    assembler = {t for t, _s, _i in spans[tracing.SERVE_BATCH_WAIT]}
    assert {t for t, _s, ids in spans[tracing.SERVE_ASSEMBLE]
            if ids["batch"]} == assembler
    assert not assembler & {t for t, _s, _i in spans[tracing.SERVE_FETCH]}


def test_slot_wait_spans_only_the_blocked_part():
    """The completion pool's span wraps the wait its ``block_s`` times,
    and is absent when a slot is free."""
    import threading

    from mx_rcnn_tpu.data.assembler import CompletionPool

    release = threading.Event()
    names = []
    real = tracing.span

    def recording(name, **ids):
        names.append((name, ids))
        return real(name, **ids)

    mp = pytest.MonkeyPatch()
    mp.setattr(tracing, "span", recording)
    try:
        with CompletionPool(1, depth=1) as pool:
            tracing.set_batch(41)
            pool.submit(release.wait)            # a free slot: no span
            assert names == []
            threading.Timer(0.05, release.set).start()
            pool.submit(lambda: None)            # blocks until the release
            assert names == [(tracing.SERVE_SLOT_WAIT, {"batch": 41})]
            assert pool.stats()["block_s"] >= 0.03
    finally:
        mp.undo()


# ------------------------------------------------------- (c) device scopes
def _scoped_names(lowered_text):
    """The name stacks in a lowered module's locations, wrappers off."""
    out = set()
    for name in re.findall(r'loc\("([^"]+)"', lowered_text):
        out.add("/".join(
            re.sub(r"^(?:[\w.]+\()+(.*?)\)+$", r"\1", c)
            for c in name.split("/")))
    return out


@pytest.fixture(scope="module")
def lowered_train_step():
    """The tiny train step lowered FOR THE TPU (no compile, no chip), with
    the Pallas kernels in: the text the chip's compiler would be given."""
    return _lowered_step(_tiny_generate_config("resnet50", "PascalVOC"))


def _lowered_step(cfg):
    """``cfg``'s train step lowered FOR THE TPU (no compile, no chip)."""
    from mx_rcnn_tpu.core.train import (
        create_train_state, make_lr_schedule, make_optimizer, make_train_step,
    )
    from mx_rcnn_tpu.models import build_model

    model = build_model(cfg)
    h, w = cfg.SHAPE_BUCKETS[0]
    g = cfg.dataset.MAX_GT_BOXES
    batch = {
        "images": jnp.zeros((2, h, w, 3)),
        "im_info": jnp.tile(jnp.array([[h, w, 1.0]]), (2, 1)),
        "gt_boxes": jnp.zeros((2, g, 5)),
        "gt_valid": jnp.zeros((2, g), bool),
    }
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.key(0), "sampling": jax.random.key(1)},
        train=True, **batch)["params"])
    tx = make_optimizer(cfg, make_lr_schedule(cfg, 10))
    state = jax.eval_shape(lambda p: create_train_state(p, tx), params)
    mp = pytest.MonkeyPatch()
    mp.setenv("MX_RCNN_TPU_PALLAS", "1")
    try:
        step = make_train_step(model, tx)
        lowered = step.trace(state, batch, jax.random.key(2)).lower(
            lowering_platforms=("tpu",))
        return lowered.as_text(debug_info=True)
    finally:
        mp.undo()


def test_roi_max_pooling_opens_its_own_scope_and_no_roi_align():
    """Under ``ROI_MODE`` ``roi_pool`` (VGG-16) the pooling's scope says
    what it is: ``roi_pool`` inside ``roi_head``, closed before flax's ``top_head`` (fc6 / fc7), and nothing under the
    name ``roi_align``, which every accepted metric file reads as
    ROIAlign.  ``roi_pool_device_ms.train``, ``roi_pool_roofline.vgg_train``
    and ``top_head_device_ms.train`` read these two components."""
    names = _scoped_names(_lowered_step(_tiny_generate_config(
        "vgg", "PascalVOC")))
    for scope in tracing.ROI_POOL_SCOPES:
        assert any(f"/{scope}/" in n + "/" for n in names), scope
    assert not any("/roi_align/" in n + "/" for n in names)
    under = [n for n in names if "/roi_pool/" in n + "/"]
    assert all("/roi_head/" in n for n in under)
    assert not any("/top_head/" in n + "/" for n in under)
    for name in ("roi_pool_device_ms.train", "roi_pool_roofline.vgg_train",
                 "top_head_device_ms.train"):
        with open(os.path.join(REPO_ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            assert json.load(f)["args"]["scope"] in tracing.ROI_POOL_SCOPES


def test_deformable_layers_and_pooling_open_their_scopes():
    """Deformable ConvNets (``resnet_dcn``): each of conv5's three
    deformable layers opens ``deform_conv`` inside flax's
    ``backbone/stage4/unit<i>``; the two pooling passes and the offset fc
    between them lie under ``deform_roi_pool`` inside ``roi_head``, closed
    before ``top_head``; nothing is named ``roi_align``.  The cell's four
    new metric files read these scopes."""
    names = _scoped_names(_lowered_step(_tiny_generate_config(
        "resnet_dcn", "PascalVOC")))
    for scope in tracing.DCN_SCOPES:
        assert any(f"/{scope}/" in n + "/" for n in names), scope
    assert not any("/roi_align/" in n + "/" for n in names)
    conv = [n for n in names if "/deform_conv/" in n + "/"]
    assert {re.search(r"/backbone/stage4/(unit\d)/", n).group(1)
            for n in conv} == {"unit1", "unit2", "unit3"}
    pool = [n for n in names if "/deform_roi_pool/" in n + "/"]
    assert all("/roi_head/" in n for n in pool)
    assert not any("/top_head/" in n + "/" for n in pool)
    assert any("/roi_offset/" in n for n in pool)
    for name in ("deform_conv_device_ms.train",
                 "deform_roi_pool_device_ms.train",
                 "deform_conv_roofline.dcn_train",
                 "deform_roi_pool_roofline.dcn_train"):
        with open(os.path.join(REPO_ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            assert json.load(f)["args"]["scope"] in tracing.DCN_SCOPES


def _kernel_paths(lowered_text, kernel):
    """The scoped names of every call of the Pallas kernel ``kernel``:
    inside a private function, the name of each call into it joined with
    the kernel's own, as the compiled step's op names join them; in the
    step's own body, the kernel's name as it stands."""
    locs = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"', lowered_text))
    own, inline, fn = {}, [], None
    for line in lowered_text.splitlines():
        head = re.match(r"\s*func\.func (?:\w+ )?@([\w.]+)\(", line)
        fn = head.group(1) if head else fn
        call = re.search(r"@tpu_custom_call\(.*loc\((#loc\d+)\)$", line)
        name = locs[call.group(1)] if call else ""
        if name.startswith(kernel + "/"):
            own.setdefault(fn, []).append(name)
        elif f"/{kernel}/" in name:
            inline.append(name)
    joined = [
        f"{locs[site]}/{name}"
        for f, names in own.items()
        for site in re.findall(rf"call @{re.escape(f)}\(.*loc\((#loc\d+)\)$",
                               lowered_text, re.M)
        for name in names]
    return sorted(path for name in joined + inline
                  for path in _scoped_names(f'loc("{name}")'))


def test_the_deformable_pair_lies_under_deform_conv():
    """Where conv5's map fits the Pallas pair (a 64 × 256 canvas: a 4 × 16
    map of 512 channels), each layer's forward and backward kernel lie
    inside its ``deform_conv`` scope, which ``deform_conv_device_ms.train``
    reads (the compiled step joins the two paths: ``…/unit<i>/deform_conv/
    jit(deform_conv_pallas)/pallas_deform_conv_fwd/…``)."""
    cfg = _tiny_generate_config("resnet_dcn", "PascalVOC")
    cfg = cfg.replace(SHAPE_BUCKETS=((64, 256),), dataset=dataclasses.replace(
        cfg.dataset, SCALES=((64, 256),)))
    text = _lowered_step(cfg)
    for kernel in ("pallas_deform_conv_fwd", "pallas_deform_conv_bwd"):
        paths = _kernel_paths(text, kernel)
        units = [re.search(r"/backbone/stage4/(unit\d)/(?:[^/]+/)*deform_conv/",
                           p) for p in paths]
        assert all(units), (kernel, paths)
        assert sorted(u.group(1) for u in units) == [
            "unit1", "unit2", "unit3"], (kernel, paths)


@pytest.mark.parametrize("scope", tracing.TRAIN_SCOPES)
def test_train_step_names_the_scope(lowered_train_step, scope):
    assert any(f"/{scope}/" in n + "/" for n in
               _scoped_names(lowered_train_step)), scope


def test_roi_align_kernels_stay_where_the_benchmark_finds_them(
        lowered_train_step):
    """XLA names a Pallas custom call after the innermost scope around it
    (measured: PERF.md, PR 25); ``roi_align_roofline.train`` finds its two
    events a step by the pattern in its metric file."""
    with open(os.path.join(REPO_ROOT, "benchmark", "metrics",
                           "roi_align_roofline.train.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    locs = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"',
                           lowered_train_step))
    kernels = [locs[m] for m in re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*loc\((#loc\d+)\)",
        lowered_train_step)]
    roi = [k for k in kernels if "/roi_head/" in k]
    assert len(roi) == 2 and len(kernels) == 3, kernels
    for k in roi:
        *_outer, innermost, primitive = k.split("/")
        assert primitive == "pallas_call"
        # the instruction's name as the trace prints it: "<scope>.N = ..."
        assert re.search(pattern, f"%{innermost}.1 = bf16[8] custom-call("
                         f'...), custom_call_target="tpu_custom_call"')
    assert [k.split("/")[-2] for k in kernels if k not in roi] == [
        "pallas_nms_mask"]


def test_roi_align_scope_holds_the_pooling_and_not_the_trunk(
        lowered_train_step):
    """``roi_align_device_share.serve`` reads the device time under this
    one component: it lies inside ``roi_head``, holds both kernels, and
    closes before ``top_head``, which ``_roi_features`` also runs."""
    names = _scoped_names(lowered_train_step)
    under = [n for n in names if "/roi_align/" in n + "/"]
    assert under and all("/roi_head/" in n for n in under), under
    assert {"pallas_roi_features_fwd", "pallas_roi_features_bwd"} <= {
        c for n in under for c in n.split("/")}
    assert not [n for n in under if "top_head" in n]
    assert [n for n in names if "top_head" in n and "/roi_head/" in n]


@pytest.mark.parametrize("scope", tracing.SERVE_SCOPES)
def test_serve_postprocess_names_the_scope(scope):
    from mx_rcnn_tpu.ops.postprocess import make_test_postprocess

    cfg = generate_config("mask_resnet_fpn", "PascalVOC")
    k, r, s = 4, 16, 28
    post = make_test_postprocess(cfg, k, 0.05, max_out=8, paste=True)
    out = {
        "rois": jnp.zeros((2, r, 4)), "roi_valid": jnp.ones((2, r), bool),
        "cls_prob": jnp.zeros((2, r, k)), "bbox_deltas": jnp.zeros((2, r, 4 * k)),
        "mask_logits": jnp.zeros((2, r, s, s, k)),
    }
    text = jax.jit(lambda o, i, h: post(o, i, h, (64, 64))).lower(
        out, jnp.ones((2, 3)), jnp.full((2, 2), 64.0)
    ).as_text(debug_info=True)
    assert any(f"/{scope}/" in n + "/" for n in _scoped_names(text)), scope


def test_serve_graph_names_its_stages(tiny_runner):
    pred = tiny_runner.predictor
    batch = tiny_runner.assemble([tiny_runner.make_request(
        np.zeros((48, 56, 3), np.float32))])
    text = pred._fn.lower(pred.params, batch).as_text(debug_info=True)
    names = _scoped_names(text)
    for scope in ("backbone", "rpn", "proposal", "roi_head", "roi_align",
                  "postprocess/decode", "postprocess/class_nms"):
        assert any(f"/{scope}/" in n + "/" for n in names), scope


# ------------------------------------------------- (d) free when off, inert
def test_span_allocates_nothing_but_the_annotation_with_no_session():
    assert not tracing.enabled()

    def many(n):
        for i in range(n):
            with tracing.span(tracing.STEP_DISPATCH, step=i, tag="step"):
                pass

    many(100)  # warm the allocator's free lists
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    many(2000)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                if s.size_diff > 0)
    # nothing is kept per call: 2000 spans leave less behind than 2000
    # of the smallest objects would
    assert grown < 2000 * 16, grown


def test_scopes_change_no_number(monkeypatch):
    """Same seed, two builds of the tiny step - one with every named scope
    a no-op: outputs and updated parameters are bit-identical."""
    import contextlib

    from mx_rcnn_tpu.core.train import (
        create_train_state, make_lr_schedule, make_optimizer, make_train_step,
    )
    from mx_rcnn_tpu.models import build_model

    cfg = _tiny_generate_config("resnet50", "PascalVOC")
    h, w = cfg.SHAPE_BUCKETS[0]
    rng = np.random.RandomState(0)
    gt = np.zeros((1, cfg.dataset.MAX_GT_BOXES, 5), np.float32)
    gt[0, 0] = [8, 8, 60, 70, 1]
    valid = np.zeros((1, cfg.dataset.MAX_GT_BOXES), bool)
    valid[0, 0] = True
    batch = {
        "images": rng.rand(1, h, w, 3).astype(np.float32),
        "im_info": np.array([[h, w, 1.0]], np.float32),
        "gt_boxes": gt, "gt_valid": valid,
    }

    def one_step():
        model = build_model(cfg)
        params = model.init(
            {"params": jax.random.key(0), "sampling": jax.random.key(1)},
            train=True, **batch)["params"]
        tx = make_optimizer(cfg, make_lr_schedule(cfg, 10))
        step = make_train_step(model, tx, donate=False)
        state, aux = step(create_train_state(params, tx), batch,
                          jax.random.key(2))
        return jax.device_get((state.params, aux))

    with_scopes = one_step()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = one_step()
    same = jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(a, b)), with_scopes, without)
    assert all(jax.tree_util.tree_leaves(same))
