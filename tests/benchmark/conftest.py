"""A compile cache of their own for the benchmark's tests.

The suite's session cache directory is inherited by every pytest-xdist
worker, and these files jit the same tiny programs at the same time in
different workers: one worker then loads an executable another is still
writing (``Function ... not found`` from XLA's CPU loader). Each test
module here gets a private directory, bound before its first jit, and
hands the session's back afterwards.
"""

import os

import pytest


@pytest.fixture(autouse=True, scope="module")
def private_compile_cache(tmp_path_factory):
    from paddle_operator_tpu import compile_cache

    before = os.environ.get("TPUJOB_COMPILE_CACHE_DIR")
    os.environ["TPUJOB_COMPILE_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cellbench-compile-cache"))
    compile_cache.enable_persistent_cache()
    yield
    if before is None:
        os.environ.pop("TPUJOB_COMPILE_CACHE_DIR", None)
    else:
        os.environ["TPUJOB_COMPILE_CACHE_DIR"] = before
    compile_cache.enable_persistent_cache()
