"""Whole cells at a tiny size on the CPU: both drivers, the check and the
last line end to end; the lower-precision control; and the timed path
broken underneath, once for each fault a cell can have, each of which
has to come out as not correct.  Nothing here is a device number."""


import numpy as np
import pytest

from harness import check_serve, check_train, rehearsal, spec

pytestmark = pytest.mark.rehearsal
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _train_cell():
    return rehearsal.tiny_cell(spec.load_cell("c4_train_b8"))


@pytest.fixture(scope="module")
def train_result():
    return rehearsal.run_cell(_train_cell(), seed=2**31 + 11, seconds=2.0)


def test_train_cell_runs_end_to_end_and_is_correct(train_result):
    r = train_result
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"train_img_per_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"      # never a device number
    for name, c in r["compared"].items():
        assert c["value"] <= c["limit"], name
    # float32 against float32 on the CPU: the copy is the same arithmetic
    assert r["compared"]["loss2_gap"]["value"] < 1e-4


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    def broken(cli):
        make = cli.make_train_step

        def factory(*a, **kw):
            step = make(*a, **dict(kw, donate=False))

            def unchanged(state, batch, rng, lr_scale=None):
                _new, aux = step(state, batch, rng)
                return state, aux

            return unchanged

        cli.make_train_step = factory

    r = rehearsal.run_cell(_train_cell(), seed=5, seconds=1.0, patch_more=broken)
    assert r["correct"] is False
    assert r["compared"]["dparam_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert r["failed"] > 0                       # steps planned, none applied


def test_half_of_the_batch_left_out_is_not_correct():
    def broken(cli):
        make = cli.make_train_step

        def factory(*a, **kw):
            step = make(*a, **kw)

            def half(state, batch, rng, lr_scale=None):
                n = next(iter(batch.values())).shape[0]
                return step(state, {k: v[: n // 2] for k, v in batch.items()},
                            rng)

            return half

        cli.make_train_step = factory

    r = rehearsal.run_cell(_train_cell(), seed=6, seconds=1.0, patch_more=broken)
    assert r["correct"] is False
    failing = [n for n, c in r["compared"].items() if not c["value"] <= c["limit"]]
    assert failing, r["compared"]


def test_the_lower_precision_control_fails_and_the_reference_passes():
    """The reference in the program's place passes against itself; computed
    on fp8 operands it fails one of the cell's numbers."""
    cell = _train_cell()
    cfg = check_train.reference_config(cell.config, cell.traffic,
                                       rehearsal.TINY)
    rng = np.random.RandomState(0)
    b, g = 2, 8
    boxes = np.zeros((b, g, 5), np.float32)
    boxes[:, 0] = [10, 12, 70, 80, 1]
    batch = {"images": rng.randn(b, 96, 96, 3).astype(np.float32) * 30,
             "im_info": np.array([[96, 96, 1.0]] * b, np.float32),
             "gt_boxes": boxes,
             "gt_valid": np.array([[True] + [False] * (g - 1)] * b)}
    import jax

    ci = {"batches": [batch] * 2, "seed": 3,
          "rng_data": jax.device_get(jax.random.key_data(jax.random.key(126)))}
    ref = check_train.reference_readings(cfg, ci, "c4", block_rows=1)
    same = check_train.readings(
        check_train.reference_readings(cfg, ci, "c4", block_rows=2), ref)
    ctl = check_train.readings(
        check_train.reference_readings(cfg, ci, "c4", round_to="float8_e4m3fn",
                                       block_rows=1), ref)
    # hand-made batches carry no record numbers: batch_gap is not theirs
    limits = {k: v for k, v in cell.limits["limits"].items()
              if k != "batch_gap"}
    assert all(ok for *_x, ok in check_train.compare(same, limits))
    assert not all(ok for *_x, ok in check_train.compare(ctl, limits))
    assert ctl["loss1_gap"] > 3 * max(same["loss1_gap"], 1e-6)


def test_a_loader_that_feeds_other_pixels_than_the_records_state_is_not_correct():
    """The reference rebuilds each checked batch from the record numbers
    its rows carry: pixels the records do not state show in batch_gap."""
    def broken(cli):
        real = cli.TrainLoader

        class Shifted(real):
            def __iter__(self):
                for batch in real.__iter__(self):
                    batch["images"] = batch["images"] + 1.0
                    yield batch

        cli.TrainLoader = Shifted

    r = rehearsal.run_cell(_train_cell(), seed=8, seconds=1.0, patch_more=broken)
    assert r["correct"] is False
    assert r["compared"]["batch_gap"]["value"] == pytest.approx(1.0, abs=1e-4)


def test_half_of_the_batch_left_out_halves_the_steps_counts():
    """The step's counts over its rows (proposals that survived, anchors
    labelled foreground) are sums: half the rows, half the count, whatever
    the loss says."""
    cell = _train_cell()
    cfg = check_train.reference_config(cell.config, cell.traffic,
                                       rehearsal.TINY)
    import jax
    from reference import data

    roidb = data.synthetic_roidb(64, cfg.dataset.NUM_CLASSES, True)
    batch = data.make_batch(roidb, [3, 70, 11, 40], cfg, (96, 96))
    ci = {"batches": [batch], "seed": 3,
          "rng_data": jax.device_get(jax.random.key_data(jax.random.key(5)))}
    ref = check_train.reference_readings(cfg, ci, "c4", block_rows=1)
    bad = check_train.reference_readings(cfg, ci, "c4", block_rows=1,
                                         fault="half_batch")
    read = check_train.readings(bad, ref)
    assert 0.3 < read["props_gap"] < 0.7


def test_half_of_the_rows_hold_far_fewer_foreground_anchors_at_full_size():
    """``fg_anchors_gap`` hangs on boxes and anchors alone, and images of
    96 px hold no foreground anchor: read here at the cell's own size, on
    the cell's own records, through the reference's target assignment."""
    import jax
    import jax.numpy as jnp

    from reference import data
    from reference.config import generate_config
    from reference.ops.anchors import shifted_anchors
    from reference.ops.targets import assign_anchor

    cell = spec.load_cell("c4_train_b8")
    cfg = generate_config(cell.config["network"], cell.config["dataset"])
    net, g = cfg.network, cfg.dataset.MAX_GT_BOXES
    anchors = jnp.asarray(shifted_anchors(
        38, 64, net.RPN_FEAT_STRIDE, ratios=net.ANCHOR_RATIOS,
        scales=net.ANCHOR_SCALES))
    count = jax.jit(jax.vmap(lambda b, v, i, k: (assign_anchor(
        anchors, b[:, :4], v, i, k, cfg).labels == 1).sum()))
    roidb = data.synthetic_roidb(512, cfg.dataset.NUM_CLASSES, True)
    rng = np.random.RandomState(0)
    for _ in range(6):
        boxes = np.zeros((8, g, 5), np.float32)
        valid = np.zeros((8, g), bool)
        for i, r in enumerate(rng.choice(len(roidb), 8, replace=False)):
            k = len(roidb[r]["boxes"])
            boxes[i, :k, :4] = roidb[r]["boxes"] * 1.25
            valid[i, :k] = True
        info = np.tile(np.array([600, 800, 1.25], np.float32), (8, 1))
        c = np.asarray(count(boxes, valid, info,
                             jax.random.split(jax.random.key(1), 8)))
        gap = abs(c[:4].sum() - c.sum()) / c.sum()
        assert gap > 3 * cell.limits["limits"]["fg_anchors_gap"], c


def test_serve_cell_runs_end_to_end():
    cell = rehearsal.tiny_cell(spec.load_cell("c4_serve_closed32"))
    r = rehearsal.run_cell(cell, seed=2**31 + 17, seconds=2.0)
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "compared"
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert r["metrics"]["serve_p95_ms"]["value"] > 0


def _serve_cell():
    return rehearsal.tiny_cell(spec.load_cell("c4_serve_closed32"))


def _broken_replies(alter):
    """patch_more: every reply of the engine goes through ``alter``."""
    def broken(cli):
        build = cli.build_stack

        def build_altered(p, args):
            stack = build(p, args)
            real = stack.runner.detections_for
            stack.runner.detections_for = lambda *a, **kw: alter(real(*a, **kw))
            return stack

        cli.build_stack = build_altered

    return broken


def _moved(dets):
    for d in dets[1:]:
        if d is not None and len(d):
            d[:, [0, 2]] += 30.0
    return dets


def _emptied(dets):
    return [None] + [d if d is None else d[:0] for d in dets[1:]]


@pytest.mark.parametrize("alter, fails", [
    (_moved, "cand_box_gap"), (_emptied, "missed_share")],
    ids=["boxes_moved", "answers_nothing"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(alter, fails):
    """Broken under the engine, through the whole harness: every reply's
    boxes moved by a third of the image; every reply emptied."""
    r = rehearsal.run_cell(_serve_cell(), seed=21, seconds=1.5,
                           patch_more=_broken_replies(alter))
    assert r["detail"]["n_reference"] > 0, "the tiny reference found nothing"
    assert r["correct"] is False
    assert r["compared"][fails]["value"] > r["compared"][fails]["limit"]


@pytest.fixture(scope="module")
def serve_raw():
    """The tiny reference's own answers for a pool of images, as
    ``calibrate`` keeps them, with the fp8 control's."""
    cell = _serve_cell()
    cfg = check_serve.reference_config(cell.config, rehearsal.TINY)
    from harness import loadgen

    pool = loadgen.make_pool(cell.traffic, 4)
    recipe = cell.traffic.get("weights")
    ref = check_serve.ReferenceDetector(cfg, "c4", 4, [(96, 96)], recipe=recipe)
    ctl = check_serve.ReferenceDetector(cfg, "c4", 4, [(96, 96)], recipe=recipe,
                                        round_to="float8_e4m3fn")
    raw = {"reference": [], "candidates": [], "reference_uncapped": []}
    for im in pool:
        raw["reference"].append(ref.detect(im))
        raw["candidates"].append(ref.last_candidates)
        raw["reference_uncapped"].append(ref.last_uncapped)
    raw["served"] = raw["reference"]
    raw["control"] = [ctl.detect(im) for im in pool]
    return cell, check_serve.rules_of(cfg), raw


def test_the_serve_control_fails_and_the_reference_passes(serve_raw):
    cell, rules, raw = serve_raw
    got = check_serve.all_readings(raw, rules, cell.limits.get("match"))
    limits = cell.limits["limits"]
    same, low = got["program"], got["control"]
    assert same["n_served"] > 0
    assert all(ok for *_x, ok in check_train.compare(same, limits)), same
    assert same["cand_box_gap"] < 1e-5 and same["cand_score_gap"] < 1e-6
    assert not all(ok for *_x, ok in check_train.compare(low, limits)), low


@pytest.mark.parametrize("fault, fails", [
    ("nms_off", "nms_overlap"), ("nms_at_0.5", "nms_overlap"),
    ("duplicated", "nms_overlap"), ("empty", "missed_share"),
    ("boxes_moved_30px", "cand_box_gap")])
def test_each_fault_of_the_postprocess_fails_the_number_made_for_it(
        serve_raw, fault, fails):
    cell, rules, raw = serve_raw
    got = check_serve.all_readings(raw, rules, cell.limits.get("match"))
    read = got[f"fault.{fault}"]
    assert read[fails] > cell.limits["limits"][fails], read


def test_a_missing_cap_and_dropped_answers_show_in_the_lists():
    """Five well-separated detections, a cap of three: the two the cap
    cuts are extra when it is skipped; every other answer dropped is
    missed.  (The tiny random detector's boxes all overlap at near-equal
    scores, so each accounts for its neighbour: made by hand here.)"""
    boxes = np.array([[10 + 40 * i, 10, 40 + 40 * i, 60] for i in range(5)],
                     np.float32)
    scores = np.array([0.9, 0.8, 0.7, 0.3, 0.2], np.float32)
    full = [None, np.hstack([boxes, scores[:, None]])]
    cands = (boxes[:, None, :].repeat(2, axis=1),
             np.stack([1 - scores, scores], axis=1))
    rules = {"nms": 0.3, "score_thresh": 0.001, "cap": 3}
    capped = [None, full[1][:3]]
    ok = check_serve.readings([capped], [capped], [cands], rules)
    assert ok["extra_share"] == 0.0 and ok["missed_share"] == 0.0
    bad = check_serve.readings([full], [capped], [cands], rules)
    # judged: the two firm ones (accounted for) and the two the cap cuts
    assert bad["extra_share"] == pytest.approx(0.5)
    assert bad["count_gap"] == pytest.approx(2 / 3)
    uncapped = dict(rules, cap=0)
    half = check_serve.FAULTS["half_dropped"](full, None, cands, uncapped)
    bad = check_serve.readings([half], [full], [cands], uncapped)
    assert bad["missed_share"] == pytest.approx(2 / 5)
    assert bad["extra_share"] == 0.0
    low = check_serve.FAULTS["scores_x0.8"](full, None, cands, uncapped)
    bad = check_serve.readings([low], [full], [cands], uncapped)
    assert bad["cand_score_gap"] == pytest.approx(0.2 * scores.mean())
    assert bad["cand_score_gap"] > 0.05 and bad["cand_box_gap"] == 0.0
