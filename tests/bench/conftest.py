"""The harness registers each traffic mix's road net with the program; put
the program's registry back after every test, so that the tests of the
registries in the same process see only the program's own entries."""
import pytest


@pytest.fixture(autouse=True)
def _restore_road_networks():
    import tiny_bench  # noqa: F401  (puts the repository on sys.path)
    from repro.fed import topology

    saved = dict(topology._ROAD_NETWORKS)
    yield
    topology._ROAD_NETWORKS.clear()
    topology._ROAD_NETWORKS.update(saved)
