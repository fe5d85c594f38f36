import os
import sys
from pathlib import Path

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device; only the dry-run uses 512 (and it sets the
# flag itself, in its own process).

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng_key():
    import jax
    return jax.random.PRNGKey(0)


def build_registry():
    """Standard 3-tier mesh used across tests."""
    from repro.core.islands import (IslandRegistry, cloud_island,
                                    edge_island, personal_island)
    reg = IslandRegistry()
    for isl in [
        personal_island("laptop", latency_ms=120, capacity_units=3.0),
        personal_island("phone", latency_ms=250, capacity_units=0.5),
        edge_island("home-nas", privacy=0.9, latency_ms=300),
        edge_island("clinic-edge", privacy=0.8, latency_ms=450,
                    datasets=("medlit",), capacity_units=6.0),
        # Scenario C firm server: owner declares P=1.0 (dedicated infra
        # under the firm's physical control, privileged data allowed)
        edge_island("firm-server", privacy=1.0, trust_cert=1.0,
                    latency_ms=350, capacity_units=8.0,
                    datasets=("caselaw-10tb",)),
        cloud_island("gpt4-api", privacy=0.4, cost=0.02, latency_ms=900),
        cloud_island("claude-api", privacy=0.5, cost=0.015, latency_ms=800),
    ]:
        reg.register(isl, reg.attestation_token(isl.island_id))
    return reg


@pytest.fixture
def registry():
    return build_registry()


@pytest.fixture
def stack(registry):
    """(registry, mist, tide, lighthouse, waves)"""
    from repro.core.lighthouse import Lighthouse
    from repro.core.mist import MIST
    from repro.core.tide import TIDE
    from repro.core.waves import WAVES, Policy
    mist = MIST()
    tide = TIDE(registry)
    lh = Lighthouse(registry)
    for i in registry.all():
        lh.heartbeat(i.island_id)
    waves = WAVES(mist, tide, lh, Policy())
    return registry, mist, tide, lh, waves
