"""Suite-wide guard against the XLA:CPU compile segfault: every xdist
worker compiles program variants for the whole suite in one process,
and past the kernel's memory-map budget the next compile crashes (see
``_property_driver.release_compiled_programs``)."""
import pytest

from _property_driver import release_compiled_programs


@pytest.fixture(autouse=True)
def _bounded_compiled_programs():
    release_compiled_programs()
    yield
