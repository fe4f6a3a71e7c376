import os
import sys

# Give the CPU test host virtual devices BEFORE jax first initializes so
# the distributed-pricing parity tests can build real 1x2 / 2x2 meshes.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.hostdev import ensure_host_devices  # noqa: E402

ensure_host_devices()

import numpy as np
import pytest

# The core engine enables jax x64 at import; import it first so every test
# module sees the same (production) numeric configuration regardless of
# collection order.
import repro.core  # noqa: F401


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def lm_request():
    """Build an admission ``Request`` from token counts, with the costs of
    a qwen2-1.5b-sized model: a KV cache of 28,672 bytes a token (K and V,
    2 bytes, 2 KV heads of 128, 28 layers) over prompt and decode, and
    2 FLOPs per active parameter (1,543,712,768) per prompt token."""
    from repro.serving import Request

    def make(rid, prompt_tokens, max_new_tokens, priority):
        return Request(rid, priority,
                       kv_bytes=28_672.0 * (prompt_tokens + max_new_tokens),
                       prefill_flops=3_087_425_536.0 * prompt_tokens)
    return make


@pytest.fixture
def strict_numerics():
    """Fail the test on ANY implicit host<->device transfer and on NaNs
    escaping jitted code.  The engine's contract (REPRO003 / IRC003) is
    that every transfer around the hot paths is explicit — jnp.asarray /
    device_put on the way in, device_get on the way out — so the jitted
    LP twin and the distributed-pricing paths must pass under a full
    transfer guard."""
    import jax
    with jax.transfer_guard("disallow"), jax.debug_nans(True):
        yield
