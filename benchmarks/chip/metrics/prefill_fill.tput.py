"""Live prompt tokens over the rows the prefill dispatches computed (prefill dispatches x max_batch x prefill_chunk), in %: the padded-row waste of chunked prefill."""
from chipbench.readings import prefill_fill as read  # noqa: F401
