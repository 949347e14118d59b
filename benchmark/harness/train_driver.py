"""Train cells: ``mx_rcnn_tpu.tools.train_end2end.train_net`` through its
own loop, loader, feed and guard, ended through its own preemption path.

One object serves set-up, the check and the window: the jitted step that
``train_net`` builds and the state it threads through it.  The driver
stands between ``train_net`` and that step (``StepHook``) and only looks:

- steps 1..``check_steps``: fetches each loss, keeps the batches and the
  sampling key as the step got them, takes per-leaf norms of the momentum
  buffer after step 1 (the first gradient as the optimizer got it:
  clipped, plus weight decay) and of the parameters' change after the last
  checked step.  The reference follows these steps later, on its own
  weights from the same seed.
- steps up to ``warm_steps``: the pipeline fills (one whole flush of the
  guard's aux window included); the last is fetched, and the window opens.
- the window: every call is dispatched untouched and time-stamped; when
  ``seconds`` have passed the last step's loss is fetched (so every step
  of the window has completed), the clock stops, and the process sends
  itself SIGTERM - ``train_net``'s preemption guard ends the loop.

Nothing compiles inside the window (``CompileClock`` is read at both
ends).  The closing checkpoint of ``train_net`` is replaced by a no-op:
it lies outside set-up and window, and would write 0.4 GB a run.
"""

from __future__ import annotations

import math
import os
import signal
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from harness.stats import window_rate

#: counts the step reports over the rows of its batch (sums), compared
#: with the reference's: a row left out shows here whatever its loss
COUNTS = ("num_fg_anchors", "num_valid_props")

#: seeds reach a little over 2**31; the program's numpy/jax seeding takes
#: 32 signed bits, so the seed is folded (same seed, same inputs)
SEED_MOD = 2**31 - 1


def leaf_norms(tree) -> Dict[str, float]:
    """{"a/b/kernel": l2 norm} of every array leaf, computed on the
    device in one call."""
    import flax
    import jax
    import jax.numpy as jnp

    flat = {
        "/".join(k): v
        for k, v in flax.traverse_util.flatten_dict(tree).items()
        if hasattr(v, "dtype")
    }
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in t.items()
    })(flat)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def momentum_of(opt_state):
    """The params-shaped momentum tree inside an optax state."""
    import jax
    import optax

    found = [
        s for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: isinstance(x, optax.TraceState))
        if isinstance(s, optax.TraceState)
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one momentum buffer, found {len(found)}")
    return found[0].trace


class StepHook:
    """See the module docstring."""

    def __init__(self, seconds: float, warm_steps: int, check_steps: int,
                 clock, trace_dir: Optional[str], batch_images: int,
                 stop: Callable[[], None]):
        self.seconds = seconds
        self.warm = max(warm_steps, check_steps)
        self.check_steps = check_steps
        self.clock = clock
        self.trace_dir = trace_dir
        self.batch_images = batch_images
        self.stop = stop
        self.k = 0
        self.batches: List[Dict[str, Any]] = []
        self.rng_data = None
        self.check_losses: List[float] = []
        self.check_counts: List[Dict[str, float]] = []
        self.grad1: Dict[str, float] = {}
        self.dparam: Dict[str, float] = {}
        self._p0 = None
        self.t0 = self.t_end = None
        self.compile_marks = [None, None]
        self.window_aux: List[Any] = []
        self.window_losses: List[float] = []
        self.dispatch_times: List[float] = []
        self.done = False
        self.in_use_max = 0

    def wrap(self, step_fn):
        import jax
        import jax.numpy as jnp

        def hooked(state, batch, rng, lr_scale=None):
            self.k += 1
            k = self.k
            if self.done:  # the loop checks its guard after each step
                return self._call(step_fn, state, batch, rng, lr_scale)
            if k == 1:
                self._p0 = jax.tree_util.tree_map(jnp.copy, state.params)
                self.rng_data = jax.device_get(jax.random.key_data(rng))
            if k <= self.check_steps:
                self.batches.append(jax.device_get(batch))
            new_state, aux = self._call(step_fn, state, batch, rng, lr_scale)
            if k <= self.check_steps:
                self.check_losses.append(float(jax.device_get(aux["loss"])))
                self.check_counts.append({
                    n: float(jax.device_get(aux[n])) for n in COUNTS
                    if n in aux})
                if k == 1:
                    self.grad1 = leaf_norms(momentum_of(new_state.opt_state))
                if k == self.check_steps:
                    delta = jax.tree_util.tree_map(
                        lambda a, b: a - b, new_state.params, self._p0)
                    self.dparam = leaf_norms(delta)
                    self._p0 = None
            if k < self.warm:
                return new_state, aux
            if k == self.warm:
                jax.block_until_ready(aux["loss"])
                self.compile_marks[0] = self.clock.mark()
                if self.trace_dir:
                    jax.profiler.start_trace(self.trace_dir)
                self.t0 = time.monotonic()
                return new_state, aux
            # inside the window
            self.window_aux.append(aux["loss"])
            now = time.monotonic()
            self.dispatch_times.append(now)
            if len(self.dispatch_times) % 8 == 1:
                stats = jax.local_devices()[0].memory_stats() or {}
                self.in_use_max = max(self.in_use_max,
                                      stats.get("bytes_in_use", 0))
            if now - self.t0 >= self.seconds:
                with jax.profiler.TraceAnnotation("bench.wait_last_step"):
                    jax.block_until_ready(aux["loss"])
                self.t_end = time.monotonic()
                self.compile_marks[1] = self.clock.mark()
                if self.trace_dir:
                    jax.profiler.stop_trace()
                self.window_losses = [
                    float(v) for v in jax.device_get(self.window_aux)
                ]
                self.window_aux = []
                self.done = True
                self.stop()
            return new_state, aux

        return hooked

    @staticmethod
    def _call(step_fn, state, batch, rng, lr_scale):
        import jax

        with jax.profiler.TraceAnnotation("bench.dispatch_step"):
            if lr_scale is None:
                return step_fn(state, batch, rng)
            return step_fn(state, batch, rng, lr_scale=lr_scale)

    @property
    def window_steps(self) -> int:
        return len(self.dispatch_times)


def _sigterm_self() -> None:
    os.kill(os.getpid(), signal.SIGTERM)


def train_argv(cell, seed: int, prefix: str) -> List[str]:
    """The entry point's arguments: the configuration's fragment, the
    traffic's fragment, the seed and a checkpoint prefix under TMPDIR."""
    return (
        list(cell.config["train_argv"]) + list(cell.traffic["argv"])
        + ["--seed", str(seed % SEED_MOD), "--prefix", prefix]
    )


def run(cell, seed: int, seconds: float, trace: bool, clock, t_process: float,
        patch_cli: Optional[Callable[[Any], None]] = None) -> Dict[str, Any]:
    """Drive one train cell.  → the run's record: counts, window times,
    what the check needs, the trace directory.  ``patch_cli``
    is the rehearsal's door (tiny config on the CPU); cells never pass it.
    """
    import jax

    from mx_rcnn_tpu.tools import train_end2end as cli

    traffic = cell.traffic
    # options the program reads from its environment (the loader's
    # assembly workers, say), stated by the mix like its argv
    os.environ.update({k: str(v) for k, v in traffic.get("env", {}).items()})
    tmp = tempfile.mkdtemp(prefix="bench_train_")
    trace_dir = os.path.join(tmp, "trace") if trace else None
    if trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    hook = StepHook(
        seconds=seconds, warm_steps=int(traffic["warm_steps"]),
        check_steps=int(traffic["check_steps"]), clock=clock,
        trace_dir=trace_dir, batch_images=int(traffic["batch_images"]),
        stop=_sigterm_self,
    )
    args = cli.parse_args(train_argv(cell, seed, os.path.join(tmp, "ckpt")))
    saved = {
        name: getattr(cli, name)
        for name in ("make_train_step", "save_checkpoint",
                     "prune_step_checkpoints", "generate_config")
    }
    if patch_cli is not None:
        patch_cli(cli)  # first: the hook then wraps whatever step is built
    make_step = cli.make_train_step
    cli.make_train_step = lambda *a, **kw: hook.wrap(make_step(*a, **kw))
    cli.save_checkpoint = lambda *a, **kw: "(benchmark: not written)"
    cli.prune_step_checkpoints = lambda *a, **kw: None
    report: Dict[str, Any] = {}
    try:
        state = cli.train_net(args, report=report)
    finally:
        for name, fn in saved.items():
            setattr(cli, name, fn)
    if not hook.done:
        raise RuntimeError(
            f"train_net returned after {hook.k} steps before the window "
            f"closed (planned: run until stopped)"
        )
    n_chips = len(jax.devices()[: cell.chips])
    images = hook.window_steps * hook.batch_images * n_chips
    bad_losses = sum(not math.isfinite(v) for v in hook.window_losses)
    guard_acts = (report["skipped_batches"] + report["retried_steps"]
                  + report["rollbacks"])
    unapplied = report["steps"] - report["steps_applied"]
    window_compiles = hook.compile_marks[1][1] - hook.compile_marks[0][1]
    del state
    return {
        "kind": "train",
        "attempted": hook.window_steps,
        "failed": min(hook.window_steps,
                      bad_losses + guard_acts + max(unapplied, 0)),
        "t0": hook.t0,
        "t_end": hook.t_end,
        "rate": window_rate(images, hook.t0, hook.t_end),
        "setup_s": hook.t0 - t_process,
        "compile_s_setup": hook.compile_marks[0][0],
        "window_compiles": window_compiles,
        "window_in_use_bytes": hook.in_use_max,
        "window_losses": hook.window_losses,
        "dispatch_times": hook.dispatch_times,
        "report": {k: v for k, v in report.items() if k != "losses"},
        "trace_dir": trace_dir,
        "tmp": tmp,
        "check_input": {
            "batches": hook.batches,
            "rng_data": hook.rng_data,
            "losses": hook.check_losses,
            "counts": hook.check_counts,
            "data": {"synthetic": int(args.synthetic),
                     "flip": not args.no_flip},
            "grad1": hook.grad1,
            "dparam": hook.dparam,
            "seed": seed % SEED_MOD,
        },
    }
