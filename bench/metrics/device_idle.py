"""Share of the window in which no operation ran on the device, in %:
1 - (union of the device's operation intervals) / window. The reader of
every `device_idle.<cell kind>` metric."""
from bench.tracefile import idle_percent as read  # noqa: F401
