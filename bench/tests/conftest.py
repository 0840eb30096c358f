"""Run the harness's tests on the CPU, with the tiny sizes of ``tiny.py``:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The persistent compilation cache stays off here, so that the tests write
nothing into the checkout.
"""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "enable_cache", lambda: "off")
