import pytest

from repro.kernels import ops


@pytest.fixture
def interpret():
    """The Pallas kernels in interpret mode, as on the chip's path."""
    before = ops.pallas_mode()
    ops.set_pallas_mode("force_interpret")
    yield
    ops.set_pallas_mode(before)
