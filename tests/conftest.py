import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — tests must see the 1 real CPU
# device.


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
