from repro.kernels.scatter_route.ops import (scatter_route_deltas,
                                            scatter_route_fits)
from repro.kernels.scatter_route.ref import scatter_route_ref
from repro.kernels.scatter_route.scatter_route import scatter_route

__all__ = ["scatter_route", "scatter_route_ref", "scatter_route_deltas",
           "scatter_route_fits"]
