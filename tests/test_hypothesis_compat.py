"""Tests for the hypothesis fallback shim itself.

The fallback branch of tests/_hypothesis_compat.py only runs where
hypothesis is absent, so CI (which installs the ``dev`` extra) would never
execute it. Here we force-load the module with hypothesis masked so the
fallback is exercised on every environment.
"""

import importlib.util
import sys
from pathlib import Path

import pytest

_SHIM_PATH = Path(__file__).with_name("_hypothesis_compat.py")


@pytest.fixture()
def shim():
    """The shim module imported with hypothesis guaranteed-absent.

    hypothesis is masked only while the shim executes: an installed
    hypothesis also registers a pytest plugin whose hooks import it during
    each test, so the mask must be lifted before the test body runs.  The
    shim has picked its fallback branch by then.
    """
    saved = {
        k: sys.modules.get(k) for k in list(sys.modules) if k.startswith("hypothesis")
    }
    for k in saved:
        del sys.modules[k]
    # None in sys.modules makes `import hypothesis` raise ImportError.
    sys.modules["hypothesis"] = None
    try:
        spec = importlib.util.spec_from_file_location(
            "_hypothesis_compat_forced_fallback", _SHIM_PATH
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        del sys.modules["hypothesis"]
        sys.modules.update(saved)
    try:
        yield mod
    finally:
        sys.modules.pop("_hypothesis_compat_forced_fallback", None)


def test_fallback_branch_selected(shim):
    assert shim.HAVE_HYPOTHESIS is False


@pytest.mark.parametrize("settings_on_top", [True, False])
def test_max_examples_honored_in_either_decorator_order(shim, settings_on_top):
    st = shim.strategies
    calls = []

    def prop(n):
        calls.append(n)

    if settings_on_top:
        wrapped = shim.settings(max_examples=7)(shim.given(n=st.integers(0, 9))(prop))
    else:
        wrapped = shim.given(n=st.integers(0, 9))(shim.settings(max_examples=7)(prop))
    wrapped()
    assert len(calls) == 7
    assert all(0 <= n <= 9 for n in calls)


def test_strategies_respect_bounds_and_kwarg_spelling(shim):
    st = shim.strategies
    seen = {"ints": [], "floats": [], "sampled": []}

    @shim.given(
        a=st.integers(min_value=3, max_value=5),
        b=st.floats(min_value=0.5, max_value=2.0),
        c=st.sampled_from([10, 20]),
    )
    @shim.settings(max_examples=25, deadline=None)
    def prop(a, b, c):
        seen["ints"].append(a)
        seen["floats"].append(b)
        seen["sampled"].append(c)

    prop()
    assert all(3 <= a <= 5 for a in seen["ints"])
    assert all(0.5 <= b <= 2.0 for b in seen["floats"])
    assert set(seen["sampled"]) <= {10, 20}


def test_failure_surfaces_the_drawn_example(shim):
    @shim.given(n=shim.strategies.integers(0, 100))
    @shim.settings(max_examples=5)
    def prop(n):
        assert n > 100  # impossible

    with pytest.raises(AssertionError, match="failed on example 0"):
        prop()


def test_draws_are_deterministic_across_runs(shim):
    runs = []
    for _ in range(2):
        drawn = []

        @shim.given(n=shim.strategies.integers(0, 10**9))
        @shim.settings(max_examples=10)
        def prop(n):
            drawn.append(n)

        prop()
        runs.append(drawn)
    assert runs[0] == runs[1]


def test_methods_receive_self(shim):
    class Holder:
        hits = 0

        @shim.given(n=shim.strategies.integers(0, 1))
        @shim.settings(max_examples=3)
        def prop(self, n):
            type(self).hits += 1

    Holder().prop()
    assert Holder.hits == 3
