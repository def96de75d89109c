"""Test support: the property-test compatibility layer and a feature-block
pool that makes misuse visible (``PoisonedBlockPool``).

The partitioner property suite (tests/test_partition_properties.py) is
written against the ``hypothesis`` API. Environments without hypothesis —
including the pinned CI image — get a small deterministic fallback that
draws seeded examples per strategy, always including both interval
endpoints, so the properties still execute everywhere instead of skipping.

Usage (drop-in for the hypothesis names used here):

    from repro.testing import given, settings, st
"""
from __future__ import annotations

import threading

import numpy as np

from repro.train.plan_io import FeatureBlockPool

try:  # pragma: no cover - exercised only where hypothesis is installed
    from hypothesis import given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    import functools
    import inspect

    class _Ints:
        def __init__(self, lo: int, hi: int):
            self.lo, self.hi = int(lo), int(hi)

        def draw(self, rng, i: int):
            if i == 0:
                return self.lo
            if i == 1:
                return self.hi
            return int(rng.integers(self.lo, self.hi + 1))

    class _Floats:
        def __init__(self, lo: float, hi: float):
            self.lo, self.hi = float(lo), float(hi)

        def draw(self, rng, i: int):
            if i == 0:
                return self.lo
            if i == 1:
                return self.hi
            return float(rng.uniform(self.lo, self.hi))

    class _Sampled:
        def __init__(self, elements):
            self.elements = list(elements)

        def draw(self, rng, i: int):
            if i < len(self.elements):  # cover every element first
                return self.elements[i]
            return self.elements[int(rng.integers(0, len(self.elements)))]

    class st:  # noqa: N801 - mimics hypothesis.strategies
        @staticmethod
        def integers(min_value: int, max_value: int) -> _Ints:
            return _Ints(min_value, max_value)

        @staticmethod
        def floats(min_value: float, max_value: float) -> _Floats:
            return _Floats(min_value, max_value)

        @staticmethod
        def booleans() -> "_Sampled":
            return _Sampled([False, True])

        @staticmethod
        def sampled_from(elements) -> "_Sampled":
            return _Sampled(elements)

    def settings(max_examples: int = 10, deadline=None, **_ignored):
        def deco(fn):
            fn._max_examples = max_examples
            return fn

        return deco

    def given(**strategies):
        def deco(fn):
            sig = inspect.signature(fn)
            kept = [
                p for name, p in sig.parameters.items()
                if name not in strategies
            ]

            @functools.wraps(fn)
            def wrapper(**fixture_kwargs):
                n = getattr(wrapper, "_max_examples", 10)
                rng = np.random.default_rng(0)
                for i in range(n):
                    drawn = {
                        name: strat.draw(rng, i)
                        for name, strat in strategies.items()
                    }
                    fn(**fixture_kwargs, **drawn)

            # hide strategy params from pytest's fixture resolution
            wrapper.__signature__ = sig.replace(parameters=kept)
            return wrapper

        return deco


class PoisonedBlockPool(FeatureBlockPool):
    """A ``FeatureBlockPool`` that makes misuse visible.

    Its blocks are 64-byte aligned, which the CPU backend's ``jnp.asarray``
    aliases rather than copies, and each block it takes back is filled with
    NaN at once. A block released while a live device array still reads
    it, or reused without every element rewritten, then shows as NaN in
    whatever reads it. ``reused`` counts the acquires that reused a block.
    """

    ALIGN = 64

    def __init__(self):
        super().__init__()
        self._count_lock = threading.Lock()
        self.reused = 0

    def acquire(self, shape, dtype):
        block, reused = super().acquire(shape, dtype)
        with self._count_lock:
            self.reused += reused
        return block, reused

    def release(self, block):
        taken = super().release(block)
        if taken:
            block.fill(np.nan)
        return taken

    def _allocate(self, shape, dtype):
        n = int(np.prod(shape)) * dtype.itemsize
        raw = np.empty(n + self.ALIGN, np.uint8)
        off = -raw.ctypes.data % self.ALIGN
        return raw[off:off + n].view(dtype).reshape(shape)
