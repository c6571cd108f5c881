"""The process-wide distributed state does not leak from test to test
(``tests/conftest.py::_default_mesh``): an xdist worker runs file after
file in one process, and a mesh left installed breaks whichever file
comes next. The two tests run in file order."""
from paddle_tpu.distributed import fleet, topology
from paddle_tpu.distributed.communication import core


def test_a_test_installs_a_mesh_and_hybrid_state():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    assert topology.axis_size("mp") == 2
    assert core.get_global_group() is not None
    assert fleet._fleet_state["hcg"] is not None


def test_the_next_test_finds_the_defaults():
    assert topology._GLOBAL_MESH is None
    assert core._DEFAULT_GROUP is None
    assert fleet._fleet_state["hcg"] is None
    assert fleet._fleet_state["strategy"] is None
    assert topology.axis_size("mp") == 1      # the default mesh, built anew
