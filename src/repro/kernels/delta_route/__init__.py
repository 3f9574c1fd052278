from repro.kernels.delta_route.delta_route import delta_route
from repro.kernels.delta_route.ops import route_deltas, route_fits
from repro.kernels.delta_route.ref import delta_route_ref

__all__ = ["delta_route", "delta_route_ref", "route_deltas", "route_fits"]
