"""The chip benchmark's yardstick: loading by name, arrivals, counts,
trace reduction, order statistics and the reference arithmetic."""
