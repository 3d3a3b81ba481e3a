"""Lane pump, submit returned to results on the host: the pump's wait for a batch's answer, its own submits of later batches and the step ahead included (host clock, not a device time); mean per lane message of the broker's latency.lane.device histogram over the window (ms)."""

from readers import lane_collect_wait_mean_ms as read  # noqa: F401
