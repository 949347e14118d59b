# Build/test glue (reference: the repo-root Makefile that ran
# `setup.py build_ext --inplace` over rcnn/cython + rcnn/pycocotools).
# The TPU rebuild has no ahead-of-time extension build — Pallas kernels
# are JIT-compiled and the C host libraries self-build into a per-user
# cache on first import — so `make native` just forces that build and
# `make test-kernels` is the SURVEY N4 kernel-vs-oracle harness.

PY ?= python

.PHONY: native test test-kernels test-fast lint check resilience chaos integration-gate clean-native

# compile native/hostops.c + native/rlelib.c into ~/.cache/mx_rcnn_tpu
native:
	$(PY) -c "from mx_rcnn_tpu.native import hostops, rle; \
	          assert hostops._lib() is not None, 'hostops build failed'; \
	          assert rle._lib() is not None, 'rlelib build failed'; \
	          print('native libraries built')"

clean-native:
	rm -f $${XDG_CACHE_HOME:-$$HOME/.cache}/mx_rcnn_tpu/*.so

# full suite (8 virtual CPU devices via tests/conftest.py); ~2h on 1
# core — the once-per-round gate.  Every test carries a wall-clock
# deadline (tests/conftest.py watchdog thread: stacks dumped, run
# aborted) so a hang fails loudly instead of stalling (VERDICT r4 #6).
test:
	$(PY) -m pytest tests/ -x -q

# Pallas kernels + geometry vs their oracles only (fast)
test-kernels:
	$(PY) -m pytest tests/test_pallas_nms.py tests/test_pallas_roi_align.py \
	      tests/test_nms.py tests/test_geometry.py tests/test_hostops.py \
	      tests/test_rle.py -q

# quick signal, <10 min on this box: the whole suite minus the
# compile-bound @slow files (parallel/distributed/gates/CLI), plus one
# named DP-correctness representative so the parallel subsystem is
# never unrepresented in the fast tier
test-fast:
	$(PY) -m pytest tests/ -m "not slow" -q

# graftlint: project-native static analysis (ANALYSIS.md) — exits
# nonzero on any unsuppressed finding or stale baseline entry.  Pure
# stdlib-ast: no jax import.
lint:
	$(PY) tools/lint.py

# the CI gate: static analysis first (seconds), then the fast tier
check: lint test-fast
	$(PY) -m pytest "tests/test_parallel.py::test_mesh_shapes" \
	      "tests/test_parallel.py::test_dp_grads_match_single_device" -q

# fault-injection resilience suite (ISSUE 1): guarded-loop rollback,
# crash-safe checkpoint fallback, loader failure budget, step watchdog —
# all driven deterministically via MX_RCNN_FAULTS, CPU-only, <1 min
resilience:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resilience.py \
	      tests/test_preemption.py -q

# chaos gate (ISSUE 9 + 12): every deterministic fault-injection
# surface in one target — the elastic loop's unit matrix plus the
# preemption, resilience, and query-of-death quarantine suites, with
# the lock-order checker armed
chaos:
	JAX_PLATFORMS=cpu MX_RCNN_LOCK_CHECK=1 $(PY) -m pytest \
	      tests/test_elastic.py tests/test_preemption.py \
	      tests/test_resilience.py tests/test_quarantine.py -q

# train→eval mAP gates on synthetic data, one per model family
# (VERDICT r3 #7): C4 flagship shape, FPN, Mask (polygon gts + segm
# protocol), VGG, and a data-parallel C4 gate over 8 virtual devices.
# FPN-family lr 5e-4 = measured stability limit for random-init
# frozen-BN after moment calibration (utils/bn_calibrate.py); FPN/mask
# TARGETS are the currently-measured random-init plateaus (the stride-4
# anchor pool saturates the fg/bg IoU boundary and the head carries an
# irreducible label-churn CE floor ≈0.6 — see integration_gate.py's
# gate_cfg notes), not aspirations: raising them is open perf work.
integration-gate:
	$(PY) -m mx_rcnn_tpu.tools.integration_gate --network resnet50
	$(PY) -m mx_rcnn_tpu.tools.integration_gate --network resnet_fpn --lr 5e-4 --steps 1200 --eval_every 200 --target 0.5
	$(PY) -m mx_rcnn_tpu.tools.integration_gate --network mask_resnet_fpn --lr 5e-4 --steps 1200 --eval_every 200 --target 0.3
	$(PY) -m mx_rcnn_tpu.tools.integration_gate --network vgg --lr 1e-3 --target 0.5
	$(PY) -m mx_rcnn_tpu.tools.integration_gate --network resnet50 --cpu 8 --dp 8 --steps 200 --target 0.5
