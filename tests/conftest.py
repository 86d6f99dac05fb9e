"""Shared pytest hooks.

``fuzz`` marker routing (pytest.ini): hypothesis tags every ``@given``
test with a ``hypothesis`` keyword — mirror it as our own ``fuzz``
marker so CI can split the suite.  The deterministic core job runs
``pytest -m "not legacy and not fuzz"``; the separate *blocking* fuzz
job runs ``pytest -m fuzz``; the local tier-1 command
(``pytest -m "not legacy"``) still runs both.
"""

import pytest


def pytest_collection_modifyitems(config, items):
    for item in items:
        if "hypothesis" in item.keywords:
            item.add_marker(pytest.mark.fuzz)


@pytest.fixture(scope="session")
def chip_smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module (its
    entry point runs only under ``__main__``)."""
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
