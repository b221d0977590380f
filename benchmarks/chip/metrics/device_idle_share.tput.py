"""Share of the traced window in which no operation ran on the device (1 - union of device op intervals / window), in %."""
from chipbench.readings import device_idle_share as read  # noqa: F401
