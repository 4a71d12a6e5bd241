import os

# Smoke tests and benches must see ONE device (a test that needs several
# sets them in its own subprocess).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line("markers",
                            "slow: long-running integration test")
