import pytest


@pytest.fixture(scope="session")
def tpch_small():
    """TPC-H at SF 0.01 (60k lineitem rows)."""
    from repro.tpch import generate
    return generate(sf=0.01, seed=7)


@pytest.fixture(scope="session")
def tpch_tiny():
    """TPC-H at SF 0.002: small enough for Pallas in interpret mode."""
    from repro.tpch import generate
    return generate(sf=0.002, seed=11)
