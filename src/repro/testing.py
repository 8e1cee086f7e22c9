"""Test helpers importable under pytest's rootdir rules (``pythonpath = src``).

Two things live here:

* :func:`make_toy_problem` — the small random-but-sane allocation problem used
  across the unit/property tests and the fleet benchmarks (it moved out of
  ``tests/conftest.py`` so test modules in subdirectories don't need relative
  imports, which pytest's rootdir-based collection forbids).

* a small, deterministic property-test core standing in for the parts of
  ``hypothesis`` the test suite uses. Tests import hypothesis (installed
  with jax 0.9.0; ``tests/conftest.py`` loads its one settings profile)
  and fall back to this shim where it is missing, so property tests still
  sweep a deterministic sample of the input space instead of being
  skipped wholesale. The shim's contract (all test-enforced in
  ``tests/test_testing_shim.py``):

  - ``strategies`` mirrors ``hypothesis.strategies``: ``integers`` /
    ``floats`` / ``booleans`` / ``sampled_from`` / ``tuples`` / ``lists``
    plus a ``@composite`` combinator for structured draws.
  - draws are DETERMINISTIC: seeded per test name, so a failure reproduces
    run-to-run and across machines (no shrinking — determinism plays that
    role).
  - ``@given`` surfaces the COUNTEREXAMPLE: when a drawn example raises, the
    failing draw (seed + example index + kwargs) is printed before the
    exception propagates, hypothesis-style ("Falsifying example: ...").
  - ``@settings(max_examples=N)`` stacks with ``@given`` in either decorator
    order.
"""
from __future__ import annotations



import numpy as np


def make_toy_problem(seed=0, m=3, n=12, p=2, alpha=0.02, beta3=10.0,
                     demand_scale=1.0, gamma=0.005):
    """Small random-but-sane allocation problem for unit/property tests."""
    from repro.core import AllocationProblem, PenaltyParams

    rng = np.random.default_rng(seed)
    K = rng.uniform(0.2, 2.0, size=(m, n)).astype(np.float32)
    c = (K.sum(axis=0) * rng.uniform(0.05, 0.2, size=n)).astype(np.float32)
    E = np.zeros((p, n), np.float32)
    E[rng.integers(0, p, size=n), np.arange(n)] = 1.0
    d = (rng.uniform(1.0, 4.0, size=m) * demand_scale).astype(np.float32)
    params = PenaltyParams.create(alpha=alpha, beta1=1.0, beta2=0.1,
                                  beta3=beta3, gamma=gamma)
    return AllocationProblem.create(K, E, c, d, params=params, ub_default=100.0)


# ---------------------------------------------------------------------------
# hypothesis fallback shim (deterministic sampling, no shrinking)
# ---------------------------------------------------------------------------


class _Strategy:
    """A value source: ``sample(rng)`` draws one value from the shared
    deterministic generator. Composable — the combinator strategies
    (``tuples`` / ``lists`` / ``composite``) hold other strategies and
    thread the SAME rng through them, so a whole structured draw is a pure
    function of the rng state."""

    def __init__(self, sampler):
        self._sampler = sampler

    def sample(self, rng):
        return self._sampler(rng)


def _shim_seed(name: str) -> int:
    """The deterministic per-test seed (a pure function of the test name —
    stable across runs, machines and test orderings). Hashed through
    sha256 so EVERY character matters: the seed-era scheme
    (``int.from_bytes(...) % 2**32``) silently collapsed to the first four
    bytes, giving any two tests with a shared 4-char prefix identical draw
    streams."""
    import hashlib
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4],
                          "little")


class strategies:  # mirrors `from hypothesis import strategies as st`
    """Deterministic stand-ins for the ``hypothesis.strategies`` the test
    suite draws from. Every method returns a :class:`_Strategy`; bounds are
    INCLUSIVE on both ends (matching hypothesis's integers/floats)."""

    @staticmethod
    def integers(min_value, max_value):
        """Uniform integer in [min_value, max_value] (inclusive)."""
        return _Strategy(lambda rng: int(rng.integers(min_value, max_value + 1)))

    @staticmethod
    def floats(min_value, max_value):
        """Uniform float in [min_value, max_value]."""
        return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    @staticmethod
    def booleans():
        """True or False, a coin flip per draw."""
        return _Strategy(lambda rng: bool(rng.integers(0, 2)))

    @staticmethod
    def sampled_from(elements):
        """One of ``elements`` (materialized once, like hypothesis does —
        so generators are safe to pass)."""
        pool = list(elements)
        assert len(pool) > 0, "sampled_from needs a non-empty collection"
        return _Strategy(lambda rng: pool[int(rng.integers(0, len(pool)))])

    @staticmethod
    def tuples(*strats):
        """A tuple drawing each element from its own strategy, in order."""
        return _Strategy(
            lambda rng: tuple(s.sample(rng) for s in strats))

    @staticmethod
    def lists(elements, min_size=0, max_size=10):
        """A list of ``elements`` draws with length in
        [min_size, max_size] (length drawn first, then the items)."""
        assert 0 <= min_size <= max_size, (min_size, max_size)

        def sampler(rng):
            k = int(rng.integers(min_size, max_size + 1))
            return [elements.sample(rng) for _ in range(k)]

        return _Strategy(sampler)

    @staticmethod
    def composite(fn):
        """``@st.composite``-style combinator: ``fn(draw, *args, **kwargs)``
        builds one structured value by calling ``draw(strategy)`` as many
        times as it likes; the decorated function becomes a strategy
        FACTORY (call it — with any extra args — to get the strategy)."""

        def factory(*args, **kwargs):
            return _Strategy(
                lambda rng: fn(lambda s: s.sample(rng), *args, **kwargs))

        factory.__name__ = getattr(fn, "__name__", "composite")
        factory.__doc__ = fn.__doc__
        return factory


# hypothesis also exposes the combinator at module level
composite = strategies.composite


def settings(max_examples=10, deadline=None, **_ignored):
    """Set the example budget on the test it decorates. Stacks with
    :func:`given` in either order — ``@given`` reads the attribute off both
    its own wrapper (``@settings`` outermost) and the wrapped test
    (``@settings`` innermost)."""
    def deco(fn):
        fn._max_examples = max_examples
        return fn
    return deco


def given(**strategy_kw):
    """Run the test once per deterministic draw (seeded per test name).

    On a failing example the counterexample is printed — seed, example
    index, and the exact kwargs of the draw — before the exception
    re-raises, so a property failure is as actionable as hypothesis's
    "Falsifying example" (determinism replaces shrinking: rerunning
    reproduces the identical draw sequence).

    The wrapper must NOT expose the wrapped signature (no ``functools.wraps``):
    pytest would otherwise read the strategy parameters as fixture requests.
    """
    def deco(fn):
        def wrapper():
            n_examples = getattr(wrapper, "_max_examples",
                                 getattr(fn, "_max_examples", 10))
            seed = _shim_seed(fn.__name__)
            rng = np.random.default_rng(seed)
            for i in range(n_examples):
                draw = {k: s.sample(rng) for k, s in strategy_kw.items()}
                try:
                    fn(**draw)
                except Exception:
                    args = ", ".join(f"{k}={v!r}" for k, v in draw.items())
                    print(f"\nFalsifying example (example {i + 1} of "
                          f"{n_examples}, seed={seed}): "
                          f"{fn.__name__}({args})")
                    raise
        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        return wrapper
    return deco
