"""Router decode, results on the host to the response handed to the C++ host: mean per lane message of latency.lane.decode over the window (ms)."""

from readers import router_decode_mean_ms as read  # noqa: F401
