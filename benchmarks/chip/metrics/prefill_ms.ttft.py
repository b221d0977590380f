"""Host-clock time per prefill dispatch, ended by block_until_ready (engine counters prefill_time_s / prefill_dispatches), in ms."""
from chipbench.readings import prefill_ms as read  # noqa: F401
