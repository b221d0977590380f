"""Engine-clock host time per engine tick outside the two waits on the model step: (step_time_s - prefill_wait_s - decode_wait_s) / steps, from the engine.step, engine.prefill.wait and engine.decode.wait spans, in ms."""
from chipbench.spans import engine_host_ms as read  # noqa: F401
