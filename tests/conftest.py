"""Shared test fixtures: the hypothesis-or-fallback shim.

Property tests import ``given``/``settings``/``st`` from here and PASS
either way.  With hypothesis installed they get real shrinking/coverage;
without it (the pinned CI image has no pip) a deterministic fallback
sampler — seeded per test name — drives the same strategies through a
fixed number of examples.  Set ``REPRO_FORCE_HYPOTHESIS_FALLBACK=1`` to
exercise the fallback path even where hypothesis is available (CI runs
the property files both ways).

The fallback supports exactly the strategy surface the suite uses:
``integers``, ``floats``, ``sampled_from``, ``lists``, ``tuples`` — and
only keyword-style ``@given(name=strategy, ...)``.  Extend it here when a
test needs more; never re-inline the shim in a test file.
"""
import importlib.util
import os
import zlib

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_chip_smoke():
    """Import the repo-root ``chip_smoke.py`` script as a module."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

_FORCE_FALLBACK = bool(os.environ.get("REPRO_FORCE_HYPOTHESIS_FALLBACK"))

try:
    if _FORCE_FALLBACK:
        raise ImportError("REPRO_FORCE_HYPOTHESIS_FALLBACK set")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # no pip install available: run the fallback sampler
    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    class _St:
        @staticmethod
        def integers(lo, hi):
            return _Strategy(lambda rng: int(rng.integers(lo, hi + 1)))

        @staticmethod
        def floats(lo, hi):
            return _Strategy(lambda rng: float(rng.uniform(lo, hi)))

        @staticmethod
        def sampled_from(seq):
            seq = list(seq)
            return _Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])

        @staticmethod
        def lists(elem, min_size=0, max_size=10):
            return _Strategy(
                lambda rng: [elem.draw(rng) for _ in
                             range(int(rng.integers(min_size, max_size + 1)))])

        @staticmethod
        def tuples(*elems):
            return _Strategy(lambda rng: tuple(e.draw(rng) for e in elems))

    st = _St()

    def settings(max_examples=6, **_kw):
        def deco(fn):
            fn._max_examples = max_examples
            return fn

        return deco

    def given(**strategies):
        def deco(fn):
            n_ex = getattr(fn, "_max_examples", 6)

            def wrapper():
                rng = np.random.default_rng(zlib.crc32(fn.__name__.encode()))
                for _ in range(n_ex):
                    fn(**{k: s.draw(rng) for k, s in strategies.items()})

            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper

        return deco


__all__ = ["HAVE_HYPOTHESIS", "given", "load_chip_smoke", "settings", "st"]
