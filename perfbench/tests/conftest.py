import jax
import pytest


@pytest.fixture(autouse=True)
def _keep_matmul_precision():
    """The paper driver sets JAX's default matmul precision for its run;
    restore it so later tests in this process see the default."""
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)
