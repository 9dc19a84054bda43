"""CPU rehearsals of the benchmark (run by hand, outside tier-1)::

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH), str(BENCH / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    import tiny

    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path_factory, monkeypatch):
    # CPU compiles are not the chip's: keep them out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))
