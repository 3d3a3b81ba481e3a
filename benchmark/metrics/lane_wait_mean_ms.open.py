"""Lane wait, park to the pump's submit of the message's batch: mean per lane message of the broker's latency.lane.wait histogram over the window (ms)."""

from readers import lane_wait_mean_ms as read  # noqa: F401
