"""Shape-bucket ladder + compile-cache accounting for online serving.

The jit cache is keyed by input shapes, so every distinct (batch, H, W)
a request stream produces is an XLA compile — seconds at tiny widths,
about a minute per flagship rung.  Serving therefore admits ONLY
shapes from a small fixed ladder (``Config.SHAPE_BUCKETS`` by default):
each incoming image is resized (dataset SCALES) and padded into the
smallest bucket that contains it, warmup precompiles the whole ladder,
and after that the engine never presents a new signature to jit.

Differences from the offline helper ``data/image.py :: pick_bucket``:
the offline path silently falls back to the largest bucket (its callers
guarantee fit by construction); a serving endpoint cannot — an oversize
request must be REJECTED (:class:`BucketOverflow`, an HTTP 4xx in a real
deployment), because "helpfully" running it would either crop pixels or
compile a fresh graph mid-traffic.

:class:`CompileCache` is the proof-of-work counter for the above: it
tracks distinct jit input signatures seen by the runner.  Because the
runner's jitted callable and params are fixed for its lifetime, a new
signature is exactly a new XLA compile, so ``misses`` after warmup must
stay 0 (asserted by tests/test_serve_runner.py).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, Sequence, Tuple

from mx_rcnn_tpu.analysis.lockcheck import make_lock


class BucketOverflow(ValueError):
    """The (resized) image does not fit any serving bucket — the request
    must be rejected, not silently cropped or freshly compiled for."""


class BucketLadder:
    """Immutable ladder of (H, W) canvas shapes, smallest-fit selection."""

    def __init__(self, buckets: Sequence[Tuple[int, int]]):
        if not buckets:
            raise ValueError("empty bucket ladder")
        uniq = {(int(h), int(w)) for h, w in buckets}
        self.buckets: Tuple[Tuple[int, int], ...] = tuple(
            sorted(uniq, key=lambda b: (b[0] * b[1], b))
        )

    def select(self, h: int, w: int) -> Tuple[int, int]:
        """Smallest-area bucket containing (h, w); raises
        :class:`BucketOverflow` when none fits."""
        for bh, bw in self.buckets:
            if bh >= h and bw >= w:
                return (bh, bw)
        raise BucketOverflow(
            f"image ({h}, {w}) exceeds every serving bucket "
            f"{list(self.buckets)} — reject the request (resize caps "
            f"should make this unreachable for in-policy inputs)"
        )

    def fits(self, h: int, w: int) -> bool:
        return any(b[0] >= h and b[1] >= w for b in self.buckets)

    def __iter__(self) -> Iterator[Tuple[int, int]]:
        return iter(self.buckets)

    def __len__(self) -> int:
        return len(self.buckets)

    def __repr__(self) -> str:
        return f"BucketLadder({list(self.buckets)})"


class CompileCache:
    """Counts distinct jit input signatures (= XLA compiles, see module
    docstring).  Thread-safe: the engine records from its worker thread
    while warmup/tests read the counters."""

    def __init__(self):
        self._lock = make_lock("CompileCache._lock")
        self._keys: set = set()
        self.hits = 0
        self.misses = 0

    def record(self, key) -> bool:
        """Note one jit call with signature ``key``; returns True on a
        cache hit (no compile)."""
        with self._lock:
            if key in self._keys:
                self.hits += 1
                return True
            self._keys.add(key)
            self.misses += 1
            return False

    @property
    def keys(self) -> Tuple:
        with self._lock:
            return tuple(sorted(self._keys, key=repr))

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "signatures": sorted(map(list, self._keys)),
            }
