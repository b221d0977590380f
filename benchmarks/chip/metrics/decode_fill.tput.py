"""Decode rows that carried a live sequence over the rows dispatched (decode dispatches x max_batch), in %."""
from chipbench.readings import decode_fill as read  # noqa: F401
