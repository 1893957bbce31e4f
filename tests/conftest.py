import importlib.util
import os

import jax
import pytest

# Tests run on the single CPU device; a test that needs several virtual
# devices starts its own subprocess with XLA_FLAGS set before jax loads.
jax.config.update("jax_enable_x64", False)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def run_bench():
    """``run_bench("async_bench")`` loads ``benchmarks/async_bench.py`` by
    path and returns the rows of its ``main(quick=True)``: the suite's
    acceptance experiment at smoke size, for a module fixture to run once
    and its tests to hold to the suite's bounds."""
    def run(name: str) -> list[dict]:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(_ROOT, "benchmarks", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main(quick=True)

    return run
