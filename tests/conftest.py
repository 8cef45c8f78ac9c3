import jax
import pytest

# Tests run on the single real CPU device (the dry-run is the ONLY place that
# forces 512 placeholder devices, via its own XLA_FLAGS header — do not set
# device-count flags here).

jax.config.update("jax_enable_x64", False)


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)
