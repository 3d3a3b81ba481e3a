"""Host deliver, hand-off to the subscriber writes flushed in the C++ host: mean per lane message of latency.native.lane_deliver over the window (ms)."""

from readers import host_deliver_mean_ms as read  # noqa: F401
