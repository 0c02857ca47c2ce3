"""Launch layer: meshes, sharding rules, abstract inputs, the compile
cache and the federated training driver."""
from repro.launch.mesh import (data_axis_size, fsdp_axes, make_host_mesh,
                               num_chips)

__all__ = ["make_host_mesh", "fsdp_axes", "data_axis_size", "num_chips"]
