"""Engine-clock time of the engine.sample span (the sampler's eager programs up to the sampled tokens on the host) per decode dispatch (engine counters sample_time_s / decode_dispatches), in ms."""
from chipbench.spans import sample_ms as read  # noqa: F401
