"""Host-clock time per decode dispatch, ended by the sampled tokens' readback (engine counters decode_time_s / decode_dispatches), in ms."""
from chipbench.readings import decode_step_ms as read  # noqa: F401
