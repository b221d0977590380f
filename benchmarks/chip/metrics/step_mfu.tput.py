"""Model FLOPs the served tokens of the traced interval need (live prompt and decode tokens, attention over live context, logits where sampled) over the traced window at the chip's peak bf16 FLOP/s, in %."""
from chipbench.readings import step_mfu as read  # noqa: F401
