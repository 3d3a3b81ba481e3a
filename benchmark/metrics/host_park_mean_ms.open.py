"""Host park, socket read to lane park in the C++ host: mean per lane message of the broker's latency.native.lane_park histogram over the window (ms)."""

from readers import host_park_mean_ms as read  # noqa: F401
