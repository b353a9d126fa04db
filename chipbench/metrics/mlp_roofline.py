"""Roofline share of the work under the `mlp` scope, in % (chipbench.readings.roofline_share)."""

from chipbench.readings import roofline_share


def read(m):
    return roofline_share(m, "mlp")
