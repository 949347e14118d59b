"""BENCHMARK.json and the data files: everything loads, names keep to the
allowed characters, and a cell, a configuration, a traffic mix and a
metric can each be added as new files plus entries."""

import glob
import json
import os
import re
import shutil

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.load_benchmark()


def _files(sub, ext="json"):
    return sorted(glob.glob(os.path.join(spec.BENCH_DIR, sub, f"*.{ext}")))


@pytest.mark.parametrize(
    "path", _files("configs") + _files("traffic") + _files("metrics")
    + _files("limits"), ids=os.path.basename)
def test_every_data_file_loads_and_is_named_plainly(path):
    with open(path) as f:
        assert isinstance(json.load(f), dict)
    assert re.match(r"^[A-Za-z0-9_.\-]+$", os.path.basename(path))


def test_names_and_units_use_only_the_allowed_characters():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]])
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_readers(workload):
    cell = spec.load_cell(workload)
    assert cell.traffic["kind"] in ("train", "serve")
    assert cell.chips == cell.traffic["chips"] in (1, 4)
    assert set(cell.limits["limits"]), "a cell compares at least one number"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    cfg_entry = {c["name"]: c for c in BENCH["configs"]}[cell.config["name"]]
    assert cfg_entry["reduced"] == cell.config["reduced"]


def test_configs_are_each_used_and_files_lie_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))


def test_a_cell_a_config_a_mix_a_metric_and_a_graph_are_added_as_files(tmp_path):
    """A later PR's move: new files and new entries, no edit of a file that
    is there (checked by hashing every old file before and after).  The
    new configuration is of another graph: its FLOP count and its
    reference model are files found by that name."""
    import hashlib

    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    old = {p: hashlib.sha1(open(p, "rb").read()).hexdigest()
           for p in glob.glob(str(root / "benchmark" / "**" / "*.*"),
                              recursive=True)}
    bench = json.loads(json.dumps(BENCH))
    b = root / "benchmark"
    cfg = json.load(open(b / "configs" / "frcnn_r101_c4_voc.json"))
    cfg["name"] = "toy_config"
    cfg["model"] = {"graph": "toy", "width": 3}
    (b / "graphs" / "toy.py").write_text(
        "from harness.flops import Layer, Pool\n"
        "def layers(model, h, w, rois):\n"
        "    return [Layer('only', 2.0 * h * w * model['width'], True, False)]\n"
        "def roi_align_pools(model, h, w, rois):\n"
        "    return [Pool(h, w, model['width'], rois, 7, 7, 2)]\n")
    (b / "reference" / "models" / "toy.py").write_text(
        "def build(cfg):\n    return ('toy model of', cfg)\n")
    (b / "configs" / "toy_config.json").write_text(json.dumps(cfg))
    mix = json.load(open(b / "traffic" / "train_bf16_b8.json"))
    mix["batch_images"] = 4
    (b / "traffic" / "toy_mix.json").write_text(json.dumps(mix))
    (b / "limits" / "toy_cell.json").write_text(
        json.dumps({"control": "float8_e4m3fn", "limits": {"loss1_gap": 0.1}}))
    (b / "metrics" / "toy_reader.py").write_text(
        "def answer(ctx, scale):\n    return scale * ctx['run']['rate']\n")
    (b / "metrics" / "toy_metric.json").write_text(
        json.dumps({"reader": "toy_reader:answer", "args": {"scale": 2}}))
    bench["configs"].append({"name": "toy_config", "source": "a paper",
                             "file": "benchmark/configs/toy_config.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toy_cell", "config": "toy_config",
                               "traffic": "toy_mix", "chips": 1, "why": "toy"})
    bench["per_layer"].append({
        "name": "toy_metric", "unit": "img/s", "better": "higher",
        "source": "host_clock", "layer": "step", "moves": "train_img_per_s",
        "workloads": ["toy_cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_img_per_s":
            m["workloads"] = m["workloads"] + ["toy_cell"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("toy_cell", root=str(root))
    assert cell.config["name"] == "toy_config"
    from harness import flops

    assert flops.train_flops(cell.config, 4, 5, 9, bench_dir=str(b)) == 2 * 120.0
    least = flops.roi_align_least_s(cell.config, 4, 5, 9, 2, False, 1e12, 1e9,
                                    bench_dir=str(b))
    assert least["bytes"] == 2 * 3 * (20 + 9 * 49)
    from reference.models import build_model

    assert build_model("cfg", "toy", str(b / "reference" / "models")) == (
        "toy model of", "cfg")
    with pytest.raises(NotImplementedError):
        build_model("cfg", "toy")        # not among the files that are there
    # the metrics of the C4 kernels name their cells and stay out of this one
    assert "roi_align_roofline.train" not in {m["name"] for m in cell.per_layer}
    assert "step_mfu.train" in {m["name"] for m in cell.per_layer}
    assert cell.traffic["batch_images"] == 4
    assert "toy_metric" in {m["name"] for m in cell.per_layer}
    got = spec.read_metrics(["toy_metric"], {"run": {"rate": 21.0}},
                            bench_dir=str(b))
    assert got == {"toy_metric": 42.0}
    # the cells that were there are untouched by the new metric
    old_cell = spec.load_cell("c4_train_b8", root=str(root))
    assert "toy_metric" not in {m["name"] for m in old_cell.per_layer}
    for p, digest in old.items():
        assert hashlib.sha1(open(p, "rb").read()).hexdigest() == digest, p


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    ctx = {"run": {"kind": "train", "rate": 1.0}, "trace": None}
    got = spec.read_metrics(["device_idle_share.train", "serve_img_per_s"], ctx)
    assert got == {}
