"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips (``chipbench.readers``)."""

from chipbench.readers import idle_share as read  # noqa: F401
