"""Device, serving cells: 1 - device-busy / traced window (%)."""

from bench.metrics._spans import idle_share


def read(view):
    return idle_share(view)
