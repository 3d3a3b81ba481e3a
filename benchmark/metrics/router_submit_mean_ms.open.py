"""Router submit, the model's publish_batch_submit, each message its batch's time: mean per lane message of latency.lane.submit over the window (ms)."""

from readers import router_submit_mean_ms as read  # noqa: F401
