import os
import sys

# Make src/ importable without installation (optional once `pip install -e .`
# with the pyproject is used). Do NOT set
# xla_force_host_platform_device_count here — the tests must see the single
# real CPU device.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# ---------------------------------------------------------------------------
# hypothesis shim: several modules hard-import `hypothesis` at module scope
# (test_elastic, test_oef_properties, test_placement). When the
# package is absent the import error used to kill collection of the *whole*
# module, hiding every plain pytest test in it. Install a stub that makes
# @given-decorated tests skip cleanly while everything else still runs.
# ---------------------------------------------------------------------------
try:  # pragma: no cover - trivial when hypothesis is installed
    import hypothesis  # noqa: F401
except ImportError:
    import types

    import pytest

    class _AnyStrategy:
        """Stands in for any strategy object; all composition returns self."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

        def __repr__(self):
            return "<hypothesis-stub strategy>"

    _ANY = _AnyStrategy()

    def _given(*_args, **_kwargs):
        def decorate(fn):
            def skipper(*a, **k):
                pytest.skip("hypothesis not installed: property test skipped")

            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            skipper.__module__ = fn.__module__
            return skipper

        return decorate

    def _settings(*_args, **_kwargs):
        return lambda fn: fn

    _settings.register_profile = lambda *a, **k: None
    _settings.load_profile = lambda *a, **k: None

    def _assume(_condition):
        return True

    strategies = types.ModuleType("hypothesis.strategies")
    strategies.__getattr__ = lambda name: _ANY  # PEP 562

    stub = types.ModuleType("hypothesis")
    stub.given = _given
    stub.settings = _settings
    stub.assume = _assume
    stub.strategies = strategies
    stub.HealthCheck = _ANY
    stub.example = lambda *a, **k: (lambda fn: fn)
    stub.note = lambda *a, **k: None
    stub.__stub__ = True

    sys.modules["hypothesis"] = stub
    sys.modules["hypothesis.strategies"] = strategies
