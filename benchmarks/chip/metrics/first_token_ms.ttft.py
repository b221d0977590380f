"""Engine-clock time from a request's admission to its first output token, per request that got its first token in the traced interval (engine counters first_token_s / first_tokens), in ms."""
from chipbench.spans import first_token_ms as read  # noqa: F401
